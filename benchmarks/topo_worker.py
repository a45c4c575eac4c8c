"""Subprocess worker for the ``topology`` benchmark table.

Runs in its own process because the forced host-device count must be set
before the first jax import (the parent benchmark process has already
initialized jax with 1 device).  Receives a JSON spec on argv[1]:

    {"devices": 32, "dim": 65536, "reps": 20,
     "combos": [["ring", 16], ...]}

and prints one ``TOPO_ROWS <json list>`` line: per combo, the compiled
schedule's round/message counts plus measured us/mix for the dense
(all-gather) and sparse (ppermute) collective schedules on a
``[n, dim]`` fp32 model, cycling through every phase of time-varying
stacks.  ``compile_gossip_schedule(dense_threshold=0.0)`` forces the
all-gather path through the same shard_map machinery, so the delta is
purely collective schedule, not harness.
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

from repro.core import gossip, topology as topo_lib  # noqa: E402
from repro.launch.mesh import make_debug_mesh  # noqa: E402


def time_mix(schedule, mesh, tree, *, reps: int) -> float:
    mix = jax.jit(lambda t, tr: gossip.mix_sparse_shardmap(
        tr, schedule=schedule, t=t, mesh=mesh, axis_name="data"))
    n_phases = len(schedule.phases)
    out = mix(jnp.asarray(0, jnp.int32), tree)
    jax.block_until_ready(out)  # compile
    t0 = time.time()
    for r in range(reps):
        out = mix(jnp.asarray(r % n_phases, jnp.int32), out)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def main() -> None:
    rows = []
    for name, n in SPEC["combos"]:
        topo = topo_lib.get_topology(name, n)
        mesh = make_debug_mesh(shape=(topo.n,), axes=("data",))
        sparse = gossip.compile_gossip_schedule(topo)
        dense = gossip.compile_gossip_schedule(topo, dense_threshold=0.0)
        tree = {"p": jax.random.normal(jax.random.PRNGKey(0),
                                       (topo.n, SPEC["dim"]))}
        us_dense = time_mix(dense, mesh, tree, reps=SPEC["reps"])
        us_sparse = time_mix(sparse, mesh, tree, reps=SPEC["reps"])
        rows.append({
            "label": f"{name}{topo.n}",  # registry name + n (unique)
            "topo": topo.name, "n": topo.n,
            "phases": len(sparse.phases),
            "rounds": sparse.max_rounds,
            "fallback_dense": sparse.any_dense,
            "msgs_sparse": sparse.messages_per_step(),
            "msgs_dense": sparse.dense_messages_per_step(),
            "bytes_ratio": (sparse.dense_messages_per_step()
                            / max(sparse.messages_per_step(), 1e-9)),
            "us_dense": us_dense, "us_sparse": us_sparse,
        })
    platform = jax.devices()[0].platform
    print("TOPO_ROWS " + json.dumps(
        [dict(r, platform=platform) for r in rows]))


if __name__ == "__main__":
    main()
