"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` shortens training
runs; ``--only <name>`` selects a single table.

  table1    heterogeneity sweep (alpha x method, ring-16)      [Table 1]
  table2    D^2 / gradient-tracking comparison                 [Table 2]
  table4    time-varying 1-peer exponential graph vs ring      [Table 4]
  table5    DSGD-variant ablation zoo                          [Table 5]
  table6    decentralized Adam variants                        [Table 6]
  fig3      average-consensus speedup                          [Fig. 3]
  fig6      topology scales (ring n in {8,16,32})              [Fig. 6/T7]
  comm      compressed gossip (CHOCO/EF) vs dense: bytes-on-wire + us/step
  loop      python-loop vs lax.scan-fused training steps/sec
  telemetry in-graph telemetry overhead: ring-8 scan-fused loop with
            telemetry off vs cadence-on (every collector, memory sink);
            the CI gate holds overhead_pct <= 5 (DESIGN.md §10)
  topology  compiled sparse ppermute schedule vs dense all-gather:
            bytes-on-wire + mixes/sec per topology (subprocess w/ forced
            host devices; DESIGN.md §7)
  runtime   execution backends (DESIGN.md §9): vmap (node-stacked) vs
            sharded (whole step in one shard_map) at ring n in {8,16,32}:
            steps/s + peak per-device TrainState bytes (subprocess w/
            forced host devices; sharded bytes must be constant in n)
  scenario  thousand-node engine (DESIGN.md §11): hybrid (node-batched
            blocks) vs vmap steps/s at ring n in {256,1024}, QG vs DSGDm
            eval loss at n=1024 / Dirichlet(0.1), churn-run determinism
            (subprocess w/ 8 forced host devices)
  serving   batched prefill+decode throughput (reduced archs)
  serve     continuous-batching engine vs sequential dense-cache baseline
            on one seeded mixed-length request set: tokens/s, p50/p95
            per-token latency, peak paged-cache bytes (subprocess; tokens
            checked bit-identical before timing; the CI gate holds
            engine tokens/s >= 1.5x sequential at n_slots=8)
  kernels   Pallas kernel microbench vs jnp reference

``--json <path>`` additionally writes every row to a machine-readable JSON
list (``BENCH_*.json`` convention) for trajectory tracking.  Every exported
row is stamped with ``schema_version``, ``timestamp`` (caller-supplied via
``--timestamp`` — e.g. CI passes its run date — empty otherwise) and
``git_rev`` so rows from different PRs/commits are directly comparable.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import time

from .common import ROWS, bench_loop, bench_telemetry, csv_row, \
    run_decentralized

#: bump when the exported row shape changes incompatibly
BENCH_SCHEMA_VERSION = 2


def _worker_rows(module: str, marker: str, spec: dict) -> list[dict]:
    """Run ``benchmarks.<module>`` in a child process (each worker pins
    itself to the CPU: forced host devices or interpret-mode kernels) and
    return the rows of its ``<marker> <json list>`` line."""
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    res = subprocess.run(
        [sys.executable, "-m", f"benchmarks.{module}", json.dumps(spec)],
        capture_output=True, text=True, timeout=3600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    lines = [ln for ln in res.stdout.splitlines()
             if ln.startswith(marker + " ")]
    if not lines:
        raise RuntimeError(f"{module} failed: {res.stderr[-2000:]}")
    return json.loads(lines[0][len(marker) + 1:])


def _git_rev() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def table1(quick=False):
    steps = 120 if quick else 300
    for alpha in (10.0, 1.0, 0.1):
        for method in ("dsgd", "dsgdm_n", "qg_dsgdm_n"):
            r = run_decentralized(method, alpha=alpha, steps=steps)
            csv_row(f"table1/{method}/alpha{alpha}", r["us_per_step"],
                    f"acc={r['acc']:.4f}")


def table2(quick=False):
    steps = 120 if quick else 300
    for method in ("dsgdm_n", "gt", "gt_dsgdm_n", "d2", "d2_plus",
                   "qg_dsgdm_n"):
        for alpha in (1.0, 0.1):
            r = run_decentralized(method, alpha=alpha, steps=steps)
            csv_row(f"table2/{method}/alpha{alpha}", r["us_per_step"],
                    f"acc={r['acc']:.4f}")


def table4(quick=False):
    """Table 4: time-varying 1-peer directed exponential graph (Assran'19)
    vs fixed ring — QG generalizes to time-varying topologies."""
    steps = 120 if quick else 300
    for topo in ("ring", "exp"):
        for method in ("dsgdm_n", "qg_dsgdm_n"):
            r = run_decentralized(method, alpha=0.1, topo_name=topo,
                                  n_nodes=16, steps=steps)
            csv_row(f"table4/{method}/{topo}16/alpha0.1", r["us_per_step"],
                    f"acc={r['acc']:.4f}")


def table5(quick=False):
    steps = 120 if quick else 300
    methods = ("dsgd", "dsgdm", "dsgdm_n", "dsgdm_sync", "dsgdm_n_sync",
               "dsgdm_n_sync_global", "slowmo", "dmsgd", "qg_dsgdm",
               "qg_dsgdm_n")
    for method in methods:
        r = run_decentralized(method, alpha=0.1, steps=steps)
        csv_row(f"table5/{method}/alpha0.1", r["us_per_step"],
                f"acc={r['acc']:.4f},consensus={r['consensus']:.2e}")


def table6(quick=False):
    steps = 120 if quick else 300
    for method in ("dadam", "qg_dadam"):
        r = run_decentralized(method, alpha=0.1, steps=steps, lr=0.003)
        csv_row(f"table6/{method}/alpha0.1", r["us_per_step"],
                f"acc={r['acc']:.4f}")


def fig3(quick=False):
    from repro.core import consensus, topology
    steps = 400 if quick else 800
    for topo in (topology.ring(16), topology.ring(32),
                 topology.social_network(), topology.torus(4, 4)):
        t0 = time.time()
        hg = consensus.run_gossip(topo, steps=steps)
        hq = consensus.run_qg_consensus(topo, steps=steps)
        us = (time.time() - t0) / (2 * steps) * 1e6
        sg = consensus.steps_to_distance(hg, 1e-2)
        sq = consensus.steps_to_distance(hq, 1e-2)
        csv_row(f"fig3/{topo.name}", us,
                f"gossip_steps_to_1e-2={sg},qg_steps_to_1e-2={sq}")


def fig6(quick=False):
    steps = 120 if quick else 300
    for n in (8, 16, 32):
        for alpha in (1.0, 0.1):
            for method in ("dsgdm_n", "qg_dsgdm_n"):
                r = run_decentralized(method, alpha=alpha, n_nodes=n,
                                      steps=steps)
                csv_row(f"fig6/{method}/ring{n}/alpha{alpha}",
                        r["us_per_step"], f"acc={r['acc']:.4f}")


def comm(quick=False):
    """Compressed-gossip table: QG-DSGDm-N under CHOCO / EF compression vs
    the dense all-gather baseline.  bytes_per_round is per node per step;
    ratio is dense/compressed bytes-on-wire."""
    steps = 120 if quick else 300
    base = run_decentralized("qg_dsgdm_n", alpha=0.1, steps=steps)
    # dense wire cost: every node ships its full fp32 model once per round
    csv_row("comm/qg_dsgdm_n/dense", base["us_per_step"],
            f"acc={base['acc']:.4f},loss={base['loss']:.4f},ratio=1.0")
    cases = [
        ("topk:0.05", None, False),   # 10x, the headline operating point
        ("topk:0.01", None, False),   # ~50x, aggressive
        ("qsgd:4", None, False),      # 6.4x quantization
        ("signnorm", None, False),    # ~32x 1-bit
        ("randk:0.05", None, False),  # 10x unbiased
        ("signnorm", None, True),     # EF14 value exchange (DeepSqueeze)
    ]
    for spec, gamma, ef in cases:
        r = run_decentralized("qg_dsgdm_n", alpha=0.1, steps=steps,
                              comm=spec, comm_gamma=gamma, comm_ef=ef)
        tag = spec.replace(":", "") + ("_ef" if ef else "")
        csv_row(
            f"comm/qg_dsgdm_n/{tag}", r["us_per_step"],
            f"acc={r['acc']:.4f},loss={r['loss']:.4f},"
            f"ratio={r['comm_ratio']:.1f},"
            f"bytes_per_round={r['comm_bits_per_node'] / 8:.0f}")


def topology(quick=False):
    """Topology-compiler table: for each registry topology, the compiled
    sparse ppermute schedule (rounds, messages, us/mix) vs the dense
    all-gather baseline run through the SAME shard_map machinery.  Runs in a
    subprocess because the forced host-device count must precede jax init.
    ``bytes_ratio`` is dense/sparse point-to-point model messages per gossip
    step — the acceptance row is social32 >= 2x."""
    combos = [["ring", 8], ["ring", 16], ["ring", 32],
              ["torus", 8], ["torus", 16], ["torus", 32],
              ["exp", 8], ["exp", 16], ["exp", 32],
              ["social", 32], ["star", 16], ["complete", 16]]
    if quick:
        combos = [c for c in combos if c[1] <= 16 or c[0] == "social"]
    spec = {"devices": max(c[1] for c in combos),
            "dim": 16384 if quick else 65536,
            "reps": 15 if quick else 20, "combos": combos}
    for r in _worker_rows("topo_worker", "TOPO_ROWS", spec):
        tag = f"topology/{r['label']}"
        csv_row(f"{tag}/dense", r["us_dense"],
                f"mix_per_s={1e6 / r['us_dense']:.1f},"
                f"msgs={r['msgs_dense']:.0f}", platform=r["platform"])
        csv_row(
            f"{tag}/sparse", r["us_sparse"],
            f"mix_per_s={1e6 / r['us_sparse']:.1f},"
            f"msgs={r['msgs_sparse']:.0f},"
            f"bytes_ratio={r['bytes_ratio']:.1f},"
            f"rounds={r['rounds']},phases={r['phases']},"
            f"speedup={r['us_dense'] / r['us_sparse']:.2f},"
            f"fallback={'dense' if r['fallback_dense'] else 'sparse'}",
            platform=r["platform"])


def runtime(quick=False):
    """Execution-backend table (DESIGN.md §9): vmap (node-stacked, no mesh),
    vmap_mesh (node-stacked + per-mix shard_map — the PR-3 boundary-crossing
    path) and sharded (whole step inside ONE shard_map) on the calibrated
    qg_dsgdm_n grid point at ring n in {8, 16, 32}, plus the overlap row
    (sharded with ``overlap='delayed_1'`` — DESIGN.md §12).  ``state_bytes``
    is the peak per-device TrainState footprint — O(n) for the vmap rows,
    O(1) for sharded; the CI gates hold sharded <= vmap_mesh us/step at
    ring-16, sharded state bytes constant in n, and overlap steps/s >=
    sharded at ring-16/32.  Runs in a subprocess because the forced
    host-device count must precede jax init."""
    ns = [8, 16, 32]      # ring-32 also feeds the overlap>=sharded CI gate
    spec = {"devices": max(ns), "ns": ns,
            "steps": 16 if quick else 32, "chunk": 8,
            "batch": 8, "n_data": 1024 if quick else 2048}
    for r in _worker_rows("runtime_worker", "RUNTIME_ROWS", spec):
        csv_row(f"runtime/{r['runtime']}/ring{r['n']}", r["us_per_step"],
                f"steps_per_s={r['steps_per_s']:.1f},"
                f"state_bytes={r['state_bytes_per_device']},"
                f"loss={r['loss']:.4f}", platform=r["platform"])


def scenario(quick=False):
    """Thousand-node scenario table (DESIGN.md §11): the node-batched hybrid
    runtime vs vmap at ring n in {256, 1024} on 8 forced host devices
    (steps/s + peak per-device TrainState bytes), QG-DSGDm-N vs DSGDm-N
    held-out eval loss at n=1024 under Dirichlet(0.1), and the n1024_churn
    preset (sampling + churn + stragglers) run twice — bit-identical params
    under the same scenario seed.  CI gates (BENCH_scenario.json): hybrid
    steps/s >= vmap at n=256 and >= 1.8x vmap at n=1024 (the sparse-vs-dense
    gossip win; with physical cores behind the 8 devices the n=256 ratio
    rises toward the device count), eval_loss(QG) < eval_loss(DSGDm), and
    max_abs_param_diff == 0."""
    spec = {"devices": 8, "perf_ns": [256, 1024],
            "perf_steps": 16 if quick else 32, "perf_chunk": 8,
            "big_steps": 25 if quick else 50, "big_chunk": 5,
            "det_steps": 6 if quick else 12, "timed_reps": 2}
    for r in _worker_rows("scenario_worker", "SCENARIO_ROWS", spec):
        derived = ",".join(f"{k}={v:.6g}" if isinstance(v, float)
                           else f"{k}={v}"
                           for k, v in r.items()
                           if k not in ("tag", "us_per_step", "platform"))
        csv_row(f"scenario/{r['tag']}", r["us_per_step"], derived,
                platform=r["platform"])


def loop(quick=False):
    """Training-loop dispatch: python per-step loop vs ``lax.scan``-fused
    chunks (run_training_scanned).  Same math, same rng stream — the delta
    is pure dispatch overhead on the CPU/bench path."""
    steps = 96 if quick else 256
    for method, n_nodes, batch, lr in (("qg_dsgdm_n", 4, 8, 0.02),
                                       ("dsgdm_n", 16, 16, 0.1),
                                       ("qg_dsgdm_n", 16, 16, 0.1)):
        rows = bench_loop(method, n_nodes=n_nodes, batch=batch, steps=steps,
                          lr=lr, chunks=(8, 32))
        for r in rows:
            csv_row(f"loop/{method}/ring{n_nodes}/{r['tag']}",
                    r["us_per_step"],
                    f"steps_per_s={r['steps_per_s']:.1f},"
                    f"speedup={r['speedup']:.2f},loss={r['loss']:.4f}")


def telemetry(quick=False):
    """Telemetry-overhead table (DESIGN.md §10): the ring-8 scan-fused loop
    with telemetry off vs cadence-on (every collector, memory sink),
    interleaved best-of-N so the ≤5% CI gate on ``overhead_pct`` is
    noise-robust.  Cadence every=80 over chunk=8 — 1 chunk in 10 runs the
    collecting trace, the other 9 run the telemetry-free graph (host-gated
    cadence; a collecting chunk pays ~40% on this sub-ms MLP micro-step, so
    the amortized budget is ~chunk/every x that; on any real model the
    collectors are noise)."""
    rows = bench_telemetry(n_nodes=8, steps=160, chunk=8,
                           reps=2 if quick else 3, every=80)
    for r in rows:
        csv_row(f"telemetry/qg_dsgdm_n/ring8/{r['tag']}", r["us_per_step"],
                f"steps_per_s={r['steps_per_s']:.1f},"
                f"overhead_pct={r['overhead_pct']:.2f},"
                f"loss={r['loss']:.4f}")


def serving(quick=False):
    """Batched-decode throughput on a reduced arch (CPU timings, never a
    device metric)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.serve import generate
    from repro.models import transformer as tf

    for arch in ("tinyllama-1.1b", "gemma2-27b", "zamba2-7b"):
        cfg = get_config(arch, reduced=True)
        key = jax.random.PRNGKey(0)
        params = tf.init_lm(key, cfg)
        b, plen, glen = 8, 32, 32 if not quick else 8
        prompts = jax.random.randint(key, (b, plen), 0, cfg.vocab_size)
        img = None
        t0 = time.time()
        toks = generate(params, cfg, prompts, gen_len=glen,
                        cache_len=plen + glen, img=img)
        jax.block_until_ready(toks)
        dt = time.time() - t0
        csv_row(f"serving/{arch}-reduced", dt / (b * glen) * 1e6,
                f"tok_per_s={b * glen / dt:.1f},batch={b},gen={glen}")


def serve(quick=False):
    """Continuous-batching serve table (DESIGN.md §13): ``ServeEngine``
    (paged KV cache, 8 in-flight slots) vs the sequential dense-cache
    baseline over the same 30 seeded mixed-length requests.  The worker
    refuses to report throughput unless the engine's greedy tokens are
    bit-identical to the baseline; the CI gate holds
    ``tokens_per_s(engine) >= 1.5 x tokens_per_s(sequential)``."""
    spec = {"arch": "tinyllama-1.1b", "requests": 12 if quick else 30,
            "max_new": 16, "n_slots": 8, "page_size": 16,
            "prefill_chunk": 16, "max_len": 64}
    rows = _worker_rows("serve_worker", "SERVE_ROWS", spec)
    by_mode = {r["mode"]: r for r in rows}
    ratio = (by_mode["engine"]["tokens_per_s"]
             / by_mode["sequential"]["tokens_per_s"])
    for r in rows:
        extra = (f",p50_token_ms={r['p50_token_latency_s'] * 1e3:.3f},"
                 f"p95_token_ms={r['p95_token_latency_s'] * 1e3:.3f},"
                 f"mismatches={r['mismatches']}")
        if r["mode"] == "engine":
            extra += (f",peak_cache_bytes={r['peak_cache_bytes']},"
                      f"speedup={ratio:.2f}")
        csv_row(f"serve/{r['arch']}/{r['mode']}",
                r["wall_s"] / r["tokens"] * 1e6,
                f"tokens_per_s={r['tokens_per_s']:.1f}" + extra,
                platform=r["platform"])


def kernels(quick=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    # fused-chain loop bench (subprocess: fresh compile caches; DESIGN.md
    # §14).  Gates: fused bytes-moved <= 0.5x the unfused stage-by-stage
    # pass count on the qg_dsgdm ring-8 loop, and parity mismatches == 0.
    # model large enough (~0.5M stacked elems) that the PACK_TILE pad
    # quantum charged to the fused side stays <2% of the byte model
    spec = {"method": "qg_dsgdm", "n": 8, "steps": 8 if quick else 20,
            "d": 512, "c": 128}
    rows = _worker_rows("kernels_worker", "KERNEL_ROWS", spec)
    by_mode = {r["mode"]: r for r in rows}
    ratio = (by_mode["fused"]["bytes_moved_per_step"]
             / by_mode["unfused"]["bytes_moved_per_step"])
    for r in rows:
        extra = (f"bytes_moved_per_step={r['bytes_moved_per_step']},"
                 f"mismatches={r['mismatches']}")
        if r["mode"] == "fused":
            extra += f",bytes_ratio={ratio:.3f}"
        csv_row(f"kernels/chain_{r['method']}_ring{r['n']}/{r['mode']}",
                r["us_per_step"], extra, platform=r["platform"])

    key = jax.random.PRNGKey(0)
    reps = 3 if quick else 10

    def bench(fn, *args, **kw):
        out = fn(*args, **kw)  # compile
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(reps):
            out = fn(*args, **kw)
        jax.block_until_ready(out)
        return (time.time() - t0) / reps * 1e6

    shape = (512, 1024)
    x = jax.random.normal(key, shape)
    m = jax.random.normal(jax.random.fold_in(key, 1), shape)
    g = jax.random.normal(jax.random.fold_in(key, 2), shape)
    us_k = bench(ops.qg_local_step, x, m, g, eta=0.1, beta=0.9)
    us_r = bench(jax.jit(lambda *a: ref.qg_local_step_ref(
        *a, eta=0.1, beta=0.9, nesterov=False)), x, m, g)
    csv_row("kernels/qg_local_step_pallas_interp", us_k,
            f"jnp_ref_us={us_r:.1f}")

    eta = jnp.float32(0.1)
    us_k = bench(ops.fused_halfstep, x, m, g, eta, beta=0.9, wd=1e-4,
                 emit_m=False)
    us_r = bench(jax.jit(lambda *a: ref.fused_halfstep_ref(
        *a, beta=0.9, wd=1e-4)[0]), x, m, g, eta)
    csv_row("kernels/fused_halfstep_pallas_interp", us_k,
            f"jnp_ref_us={us_r:.1f}")

    us_k = bench(ops.gamma_correct, x, m, g, gamma=0.5)
    us_r = bench(jax.jit(lambda *a: ref.gamma_correct_ref(
        *a, gamma=0.5)), x, m, g)
    csv_row("kernels/gamma_correct_pallas_interp", us_k,
            f"jnp_ref_us={us_r:.1f}")

    xc = jax.random.normal(jax.random.fold_in(key, 20), (16, 8192))
    thr = jnp.quantile(jnp.abs(xc), 0.95, axis=1)
    us_k = bench(ops.threshold_mask, xc, thr)
    us_r = bench(jax.jit(lambda *a: ref.threshold_mask_ref(*a)), xc, thr)
    csv_row("kernels/threshold_mask_pallas_interp", us_k,
            f"jnp_ref_us={us_r:.1f}")

    scale = jnp.max(jnp.abs(xc), axis=1)
    u = jax.random.uniform(jax.random.fold_in(key, 21), xc.shape)
    us_k = bench(ops.quantize_dequantize, xc, scale, u, levels=15)
    us_r = bench(jax.jit(lambda *a: ref.quantize_dequantize_ref(
        *a, levels=15)), xc, scale, u)
    csv_row("kernels/quantize_dequantize_pallas_interp", us_k,
            f"jnp_ref_us={us_r:.1f}")

    b, s, h, kh, d = 1, 512, 8, 4, 64
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 3), (b, s, kh, d))
    v = jax.random.normal(jax.random.fold_in(key, 4), (b, s, kh, d))
    us_k = bench(ops.flash_attention, q, k, v, block_q=128, block_k=128)
    us_r = bench(jax.jit(lambda *a: ref.flash_attention_ref(*a)), q, k, v)
    csv_row("kernels/flash_attention_pallas_interp", us_k,
            f"jnp_ref_us={us_r:.1f}")

    b, s, h, p, n = 1, 512, 4, 32, 32
    xs = jax.random.normal(key, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 5),
                                           (b, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 6), (h,)) * 0.3)
    bb = jax.random.normal(jax.random.fold_in(key, 7), (b, s, n)) * 0.3
    cc = jax.random.normal(jax.random.fold_in(key, 8), (b, s, n)) * 0.3
    dsk = jnp.ones((h,))
    us_k = bench(ops.ssd_scan, xs, dt, a, bb, cc, dsk, chunk=128)
    us_r = bench(jax.jit(lambda *a_: ref.ssd_scan_ref(*a_)), xs, dt, a, bb, cc)
    csv_row("kernels/ssd_scan_pallas_interp", us_k, f"jnp_ref_us={us_r:.1f}")


TABLES = {
    "table1": table1, "table2": table2, "table4": table4, "table5": table5,
    "table6": table6, "fig3": fig3, "fig6": fig6, "comm": comm,
    "topology": topology, "loop": loop, "telemetry": telemetry,
    "runtime": runtime, "scenario": scenario, "serving": serving,
    "serve": serve, "kernels": kernels,
}


def stamp_rows(rows: list[dict], *, timestamp: str = "",
               git_rev: str | None = None) -> list[dict]:
    """Add the cross-PR comparability fields to every exported row:
    ``schema_version`` (format), ``timestamp`` (CALLER-supplied — the
    harness never invents one, so identical reruns stay byte-identical) and
    ``git_rev``.  Returns the same row dicts, stamped in place."""
    rev = _git_rev() if git_rev is None else git_rev
    for row in rows:
        row["schema_version"] = BENCH_SCHEMA_VERSION
        row["timestamp"] = timestamp
        row["git_rev"] = rev
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write all rows to PATH as a JSON list")
    ap.add_argument("--timestamp", default="", metavar="ISO8601",
                    help="caller-supplied run timestamp stamped onto every "
                         "--json row (CI passes its run date)")
    args = ap.parse_args(argv)
    names = [args.only] if args.only else list(TABLES)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for n in names:
        TABLES[n](quick=args.quick)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(stamp_rows(ROWS, timestamp=args.timestamp), f,
                      indent=1)
        print(f"# wrote {len(ROWS)} rows to {args.json}")


if __name__ == "__main__":
    main()
