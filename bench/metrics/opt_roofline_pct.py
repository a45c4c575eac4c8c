"""The optimizer chain's share of its HBM roofline: the bytes the
QG-DSGDm-N update rule needs (32 B per fp32 parameter per node: x, g and
m_hat read and the half step written; x before and after the mix and m_hat
read and m_hat written), counted from the rule and not from any kernel, over
the chip's HBM peak, as a share of the chain's device time ``opt_ms``."""


def read(r):
    s = r.summary.layer_s.get("opt", 0.0)
    if s <= 0:
        return None
    least = r.rule_bytes_per_chip_step * r.steps / r.peaks.hbm_bytes_per_s
    return 100.0 * least / s
