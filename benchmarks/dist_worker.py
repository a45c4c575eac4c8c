"""2-process ``jax.distributed`` localhost smoke (DESIGN.md §12).

Proves the multi-host plumbing end to end on one machine: two processes,
each exposing 4 forced host devices, join a ``jax.distributed`` service
(gloo CPU collectives — see ``repro.launch.distributed``), build the SAME
process-major ring-8 mesh, and run the sharded runtime with each process
feeding only its own half of the node axis
(``ShardedRuntime.put_batch`` → ``jax.make_array_from_callback``).

Acceptance: the per-node parameter shards of the 2-process run are
BIT-IDENTICAL to a single-process 8-device sharded run of the same spec.
The parameter path contains only ppermute (exact data movement) and
per-node local math — no cross-node floating-point reduction — so the
digests must match exactly; only scalar metric psums may differ in
reduction order, which is why the loss is compared with a tolerance
instead.

Usage:

    python -m benchmarks.dist_worker            # driver: spawns the three
                                                # worker processes, compares
    python -m benchmarks.dist_worker '<json>'   # one worker (internal)

The driver prints ``DIST_SMOKE_OK`` and exits 0 on success, raises on any
mismatch.  Used by tests/test_distributed.py and the CI dist-smoke step.
"""
import hashlib
import json
import os
import socket
import subprocess
import sys

STEPS = 12
N = 8


def _node_digests(params) -> dict:
    """sha256 per node id over this process's addressable parameter shards,
    leaves visited in deterministic ``jax.tree.leaves`` order.  Node id =
    the shard's start index on the leading (node) axis."""
    import jax
    import numpy as np

    hashers: dict = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            node = int(sh.index[0].start or 0)
            hashers.setdefault(node, hashlib.sha256()).update(
                np.asarray(sh.data).tobytes())
    return {str(k): h.hexdigest() for k, h in sorted(hashers.items())}


def worker(cfg: dict) -> None:
    # a CPU study by design: pin the platform so it never takes the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               f"{cfg['devices_per_proc']}")
    import jax

    if cfg["nprocs"] > 1:
        from repro.launch.distributed import initialize
        initialize(cfg["coordinator"], cfg["nprocs"], cfg["pid"])

    from repro import api
    from repro.launch.mesh import make_debug_mesh
    from repro.train import run_training_scanned

    from benchmarks.common import bench_spec

    spec = bench_spec("qg_dsgdm_n", alpha=0.1, n_nodes=N, steps=STEPS,
                      batch=4, n_data=512, runtime="sharded")
    mesh = make_debug_mesh(shape=(N,), axes=("data",))
    ex = api.build(spec, mesh=mesh)
    st, hist = run_training_scanned(ex.trainer, ex.state,
                                    ex.task.make_iter(), STEPS, chunk=4,
                                    log_every=0, log_fn=lambda *_: None)
    jax.block_until_ready(st.params)
    print("DIST_RESULT " + json.dumps({
        "pid": cfg["pid"], "nodes": _node_digests(st.params),
        "loss": float(hist[-1]["loss"])}), flush=True)
    if cfg["nprocs"] > 1:
        jax.distributed.shutdown()


def _spawn(cfg: dict) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.pop("XLA_FLAGS", None)      # the worker sets its own device count
    return subprocess.Popen(
        [sys.executable, "-m", "benchmarks.dist_worker", json.dumps(cfg)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _result(proc: subprocess.Popen, timeout: int = 600) -> dict:
    out, err = proc.communicate(timeout=timeout)
    lines = [ln for ln in out.splitlines() if ln.startswith("DIST_RESULT ")]
    if proc.returncode or not lines:
        raise RuntimeError(
            f"dist worker failed (rc={proc.returncode}): {err[-2000:]}")
    return json.loads(lines[0][len("DIST_RESULT "):])


def driver() -> None:
    # single-process reference: all 8 nodes on one process's devices
    ref = _result(_spawn({"pid": 0, "nprocs": 1, "devices_per_proc": N}))
    assert len(ref["nodes"]) == N, ref["nodes"]

    with socket.socket() as s:          # free localhost port for process 0
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    per = N // 2
    procs = [_spawn({"pid": p, "nprocs": 2, "devices_per_proc": per,
                     "coordinator": coord}) for p in range(2)]
    results = [_result(p) for p in procs]

    merged: dict = {}
    for r in results:
        merged.update(r["nodes"])
    if merged != ref["nodes"]:
        bad = [k for k in ref["nodes"] if merged.get(k) != ref["nodes"][k]]
        raise AssertionError(
            f"2-process params differ from single-process at nodes {bad}")
    for r in results:       # metric psums may reorder — tolerance, not bits
        if abs(r["loss"] - ref["loss"]) > 1e-5 * max(1.0, abs(ref["loss"])):
            raise AssertionError(
                f"loss mismatch: dist={r['loss']} ref={ref['loss']}")
    print(f"DIST_SMOKE_OK nodes={len(merged)} loss={ref['loss']:.6f}",
          flush=True)


def main() -> None:
    if len(sys.argv) > 1:
        worker(json.loads(sys.argv[1]))
    else:
        driver()


if __name__ == "__main__":
    main()
