"""Subprocess worker for the ``kernels`` benchmark table (DESIGN.md §14).

Receives a JSON spec on argv[1]:

    {"method": "qg_dsgdm", "n": 8, "steps": 20, "d": 64, "c": 10}

and prints one ``KERNEL_ROWS <json list>`` line with two rows over the SAME
seeded ring-``n`` training loop — ``unfused`` (``fused='off'``, the
stage-by-stage transform chain) and ``fused`` (``fused='pallas'``, the
packed one-pass kernels).  Each row carries:

  * ``bytes_moved_per_step``  — the analytic roofline HBM traffic model
    (``core.transforms.chain_bytes_moved``): streaming passes x bytes for
    the optimizer chain, the quantity the CI gate compares.  Single-core
    interpret-mode CI cannot see a wall-clock win (the Pallas interpreter
    only emulates the fusion), so the gate is anchored to the byte model
    the kernels provably realize on a real memory hierarchy, not to
    ``wall_s``.
  * ``xla_bytes_accessed``    — XLA's measured cost analysis for one
    optimizer step (informational; includes the gossip exchange and
    whatever the CPU backend happens to fuse, so it is NOT the gate).
  * ``mismatches``            — parameter elements where the two
    trajectories disagree beyond atol 5e-5 after ``steps`` steps; the gate
    holds this at 0 (fusion must not change the trajectory).

Wall time is reported for completeness but never gated.
"""
import json
import os
import sys
import time

# a CPU study by design: pin the platform so it never takes the chip
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import optim, topology, transforms  # noqa: E402
from repro.train import DecentralizedTrainer, run_training  # noqa: E402

SPEC = json.loads(sys.argv[1])

_ATOL = 5e-5


def _task(n, d, c):
    def init_fn(key):
        k1, _ = jax.random.split(key)
        return ({"w": jax.random.normal(k1, (d, c)) * 0.3,
                 "b": jnp.zeros(c)}, {})

    def loss_fn(p, ms, batch, rng):
        xb, yb = batch
        logits = xb @ p["w"] + p["b"]
        ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, yb[:, None].astype(jnp.int32), -1)[:, 0])
        return ce, ({}, {})

    def batches(steps, seed=0):
        rng = np.random.default_rng(seed)
        return [(rng.normal(size=(n, 16, d)).astype(np.float32),
                 rng.integers(0, c, size=(n, 16))) for _ in range(steps)]

    return init_fn, loss_fn, batches


def _run(method, fused, n, steps, d, c):
    init_fn, loss_fn, batches = _task(n, d, c)
    opt = optim.make_optimizer(method, lr=0.1, weight_decay=1e-4,
                               fused=fused)
    tr = DecentralizedTrainer(loss_fn, opt, topology.ring(n))
    state = tr.init(jax.random.PRNGKey(0), init_fn)
    data = batches(steps)
    # warm pass compiles the step; the timed pass reuses the cache
    run_training(tr, state, iter(data[:1]), 1, rng=jax.random.PRNGKey(1),
                 log_every=0, log_fn=lambda *_: None)
    state = tr.init(jax.random.PRNGKey(0), init_fn)
    t0 = time.time()
    state, _ = run_training(tr, state, iter(data), steps,
                            rng=jax.random.PRNGKey(1), log_every=0,
                            log_fn=lambda *_: None)
    jax.block_until_ready(state.params)
    wall = time.time() - t0
    return opt, state, wall


def _xla_bytes(opt, params, w):
    """XLA's 'bytes accessed' for one compiled optimizer step
    (informational — includes the gossip exchange and CPU-side fusion)."""
    try:
        def step(p, g, s):
            return opt.step(p, g, s, w=w, lr=0.1, t=0)

        grads = jax.tree.map(jnp.zeros_like, params)
        compiled = jax.jit(step).lower(params, grads,
                                       opt.init(params)).compile()
        return float((compiled.cost_analysis() or {}).get("bytes accessed",
                                                          0.0))
    except Exception:
        return 0.0


def main():
    method = SPEC.get("method", "qg_dsgdm")
    n = SPEC.get("n", 8)
    steps = SPEC.get("steps", 20)
    d, c = SPEC.get("d", 512), SPEC.get("c", 128)
    w = topology.ring(n).w()

    opt_u, st_u, wall_u = _run(method, "off", n, steps, d, c)
    opt_f, st_f, wall_f = _run(method, "pallas", n, steps, d, c)

    mismatches = int(sum(
        int(jnp.sum(jnp.abs(a - b) > _ATOL))
        for a, b in zip(jax.tree.leaves(st_u.params),
                        jax.tree.leaves(st_f.params))))

    n_elems = sum(int(np.prod(l.shape))
                  for l in jax.tree.leaves(st_u.params))
    stages = opt_u._stages()
    bytes_u = transforms.chain_bytes_moved(stages, n_elems, fused="off")
    bytes_f = transforms.chain_bytes_moved(stages, n_elems, fused="pallas")

    rows = []
    for mode, opt, st, wall, bts in (
            ("unfused", opt_u, st_u, wall_u, bytes_u),
            ("fused", opt_f, st_f, wall_f, bytes_f)):
        rows.append({
            "mode": mode, "method": method, "n": n, "steps": steps,
            "n_elems": n_elems, "wall_s": wall,
            "us_per_step": wall / steps * 1e6,
            "bytes_moved_per_step": bts,
            "xla_bytes_accessed": _xla_bytes(opt, st.params, w),
            "mismatches": mismatches,
        })
    platform = jax.devices()[0].platform
    print("KERNEL_ROWS " + json.dumps(
        [dict(r, platform=platform) for r in rows]))


if __name__ == "__main__":
    main()
