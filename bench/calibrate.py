"""Readings that a cell's limits are set from (``bench/limits/<cell>.json``).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        [--variants bf16,half_batch,no_gossip]

For each seed, in one process on the chip: the program's set-up (the same
first steps a run checks), the reference, and the compared numbers of the
sound program (the lower readings).  For each variant, on the same weights
and batches, the reference put in the program's place with the control
(``bf16``: computed in bfloat16) or a planted fault, compared with the sound
reference (the upper readings).  A state left unchanged reads 1 on
``delta_gap`` by construction and needs no run.  Prints one JSON line per
reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    cell = harness.load_cell(args.workload)
    why_not = harness.check_devices(cell.chips)
    if why_not:
        print(f"calibrate: {why_not}", file=sys.stderr)
        return 1
    harness.configure_compile_cache()
    import jax
    from bench import compare, reference

    variants = [v for v in args.variants.split(",") if v]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        su = harness.set_up(cell, seed)
        program, kept = su.program, su.feed.kept
        del su
        gc.collect()
        x0, _ = harness._init_fn(cell)(jax.random.PRNGKey(seed % (1 << 31)))
        x0 = jax.device_get(x0)
        args_ = (cell.model, cell.config, cell.traffic, x0, kept)
        sound = reference.run(*args_)
        print(json.dumps({"seed": seed, "reading": "program",
                          **compare.numbers(program, sound),
                          "seconds": time.perf_counter() - t0}), flush=True)
        for v in variants:
            kw = {"dtype": "bfloat16"} if v == "bf16" else {"fault": v}
            other = reference.run(*args_, **kw)
            print(json.dumps({"seed": seed, "reading": v,
                              **compare.numbers(other, sound)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
