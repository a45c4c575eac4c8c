"""Subprocess worker for the ``scenario`` benchmark table (DESIGN.md §11).

Runs in its own process because the forced host-device count must be set
before the first jax import.  Receives a JSON spec on argv[1]:

    {"devices": 8, "perf_ns": [256, 1024], "perf_steps": 32,
     "perf_chunk": 8, "big_steps": 25, "big_chunk": 5, "det_steps": 8}

and prints one ``SCENARIO_ROWS <json list>`` line with three row families:

* ``hybrid/nN`` vs ``vmap/nN`` — scan-fused steps/s of the node-batched
  hybrid runtime (blocks of b = n/devices nodes inside one shard_map)
  against the node-stacked vmap path on the SAME n-node ring preset, plus
  peak per-device TrainState bytes.  The hybrid advantage has two parts:
  device parallelism (needs physical cores behind the forced host devices)
  and the block-compiled sparse gossip vs vmap's dense n x n contraction
  (algorithmic — grows with n; this is what survives on an oversubscribed
  1-2 core CI host, so the perf gate pins the n=1024 ratio).
* ``qg/n1024`` vs ``dsgdm/n1024`` — the paper's headline comparison pushed
  to n=1024 under Dirichlet(0.1): held-out eval loss / acc after a short
  hybrid run (the BENCH gate pins eval_loss(QG) < eval_loss(DSGDm)).
* ``churn_determinism/n1024`` — the n1024_churn preset (client sampling +
  windowed churn + stragglers) run twice under the same scenario seed; the
  final parameter stacks must match bit-for-bit (max |diff| == 0).
"""
import json
import os
import sys

SPEC = json.loads(sys.argv[1])
# a CPU study by design: pin the platform so it never takes the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           f"{SPEC['devices']}")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.train import run_training_scanned  # noqa: E402

MESH = make_debug_mesh(shape=(SPEC["devices"],), axes=("data",))


def state_bytes_per_device(state) -> int:
    per_dev: dict = {}
    for leaf in jax.tree.leaves(state):
        if not hasattr(leaf, "addressable_shards"):
            continue
        seen = set()
        for sh in leaf.addressable_shards:
            if sh.device in seen:
                continue
            seen.add(sh.device)
            per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
    return max(per_dev.values()) if per_dev else 0


def bench_perf(n: int, runtime: str) -> dict:
    steps, chunk = SPEC["perf_steps"], SPEC["perf_chunk"]
    spec = api.presets.get("n1024_ring").override(
        f"topology.n={n}", "data.n_data=4096", f"loop.steps={steps}",
        f"loop.chunk={chunk}", "eval.enabled=False", f"runtime={runtime}")
    ex = api.build(spec, mesh=MESH if runtime == "hybrid" else None)

    def fresh():
        return jax.tree.map(jnp.copy, ex.state), ex.task.make_iter()

    st, it = fresh()   # warm-up compiles every trace (incl. the tail chunk)
    st, _ = run_training_scanned(ex.trainer, st, it, steps, chunk=chunk,
                                 log_every=0, log_fn=lambda *_: None)
    bytes_per_dev = state_bytes_per_device(st)
    wall = float("inf")
    for _ in range(SPEC.get("timed_reps", 2)):   # best-of: host noise
        st, it = fresh()
        t0 = time.time()
        st, hist = run_training_scanned(ex.trainer, st, it, steps,
                                        chunk=chunk, log_every=0,
                                        log_fn=lambda *_: None)
        jax.block_until_ready(st.params)
        wall = min(wall, time.time() - t0)
    return {"tag": f"{runtime}/n{n}", "us_per_step": wall / steps * 1e6,
            "steps_per_s": steps / wall,
            "state_bytes_per_device": bytes_per_dev,
            "loss": hist[-1]["loss"]}


def bench_method(method: str) -> dict:
    spec = api.presets.get("n1024_ring").override(
        f"optim.name={method}", f"loop.steps={SPEC['big_steps']}",
        f"loop.chunk={SPEC['big_chunk']}")
    res = api.run(spec, mesh=MESH, log_fn=lambda *_: None)
    return {"tag": f"{method}/n1024",
            "us_per_step": res.wall_time_s / max(1, res.steps_run) * 1e6,
            "eval_loss": res.final["eval_loss"], "acc": res.final["acc"],
            "mean_tv": res.heterogeneity["mean_tv"]}


def bench_determinism() -> dict:
    def once():
        spec = api.presets.get("n1024_churn").override(
            f"loop.steps={SPEC['det_steps']}", "eval.enabled=False")
        res, st = api.run(spec, mesh=MESH, log_fn=lambda *_: None,
                          with_state=True)
        flat = np.concatenate([np.asarray(l).ravel()
                               for l in jax.tree.leaves(st.params)])
        return res, flat

    r1, p1 = once()
    r2, p2 = once()
    return {"tag": "churn_determinism/n1024",
            "us_per_step": r1.wall_time_s / max(1, r1.steps_run) * 1e6,
            "max_abs_param_diff": float(np.max(np.abs(p1 - p2))),
            "alive_frac": float(r1.history[-1]["alive_frac"]),
            "loss": r1.history[-1]["loss"],
            "loss_rerun": r2.history[-1]["loss"]}


def main() -> None:
    rows = []
    for n in SPEC["perf_ns"]:
        for runtime in ("vmap", "hybrid"):
            rows.append(bench_perf(n, runtime))
    for method in ("dsgdm_n", "qg_dsgdm_n"):
        rows.append(bench_method(method))
    rows.append(bench_determinism())
    platform = jax.devices()[0].platform
    print("SCENARIO_ROWS " + json.dumps(
        [dict(r, platform=platform) for r in rows]))


if __name__ == "__main__":
    main()
