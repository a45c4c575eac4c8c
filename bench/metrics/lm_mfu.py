"""Model FLOPs utilization of the language-model training step: the model
FLOPs of the traced steps (the configuration's own count, forward and
backward; the layers the step recomputes in its backward pass are not
counted) over traced window x chips x the chip's bf16 peak, in %."""


def read(r):
    if r.window_s <= 0 or not r.flops_per_step:
        return None
    return 100.0 * r.flops_per_step * r.steps / (
        r.window_s * r.chips * r.peaks.flops_per_s)
