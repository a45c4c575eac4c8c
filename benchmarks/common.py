"""Shared decentralized-training harness for the paper-table benchmarks.

Scaled-down analogue of the paper's CIFAR-10 protocol: synthetic CIFAR-shaped
classification (data/synthetic.py), Dirichlet non-i.i.d. partition, ring /
social topologies, learning-rate warmup + stage-wise decay, evaluation =
averaged per-node accuracy on the full eval set (paper §5.1).

Every run is a declarative ``ExperimentSpec`` executed through the one
``repro.api.run`` assembly path — a benchmark row IS a named grid point, so
any table cell can be reproduced standalone with

    python -m repro.api social32_alpha0.1_qg --set loop.steps=300
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import api


def bench_spec(
    method: str, *, alpha: float, topo_name: str = "ring", n_nodes: int = 16,
    steps: int = 150, lr: float = 0.1, seed: int = 0, batch: int = 16,
    n_data: int = 4096, noise: float = 2.5, n_classes: int = 20,
    opt_kwargs: dict | None = None, comm: str | None = None,
    comm_gamma: float | None = None, comm_ef: bool = False,
    runtime: str = "auto", overlap: str = "none",
) -> api.ExperimentSpec:
    """The calibrated benchmark grid point as a spec.

    Task difficulty (noise=2.5, 20 classes) is calibrated so the paper's
    method ordering emerges: at alpha=0.1 on ring-16, DSGD << DSGDm-N <
    QG-DSGDm-N (see EXPERIMENTS.md).  ``runtime`` selects the execution
    backend (the `runtime` benchmark table passes 'vmap'/'sharded' with a
    forced host-device mesh; everything else keeps 'auto')."""
    return api.ExperimentSpec(
        name=f"bench/{method}/{topo_name}{n_nodes}/alpha{alpha}",
        seed=seed,
        runtime=runtime,
        overlap=overlap,
        data=api.DataSpec(dataset="classification", alpha=alpha, batch=batch,
                          n_data=n_data, n_classes=n_classes, hw=8,
                          noise=noise, train_frac=0.5),
        topology=api.TopologySpec(name=topo_name, n=n_nodes),
        optim=api.OptimSpec(name=method, lr=lr, weight_decay=1e-4,
                            kwargs=dict(opt_kwargs or {})),
        comm=api.CommSpec(compressor=comm or "dense", gamma=comm_gamma,
                          error_feedback=comm_ef),
        loop=api.LoopSpec(steps=steps, warmup=max(1, steps // 20),
                          decay_at=(0.5, 0.75)),
        model=api.ModelSpec(name="mlp"),
    )


def run_decentralized(method: str, **kw) -> dict:
    """Train one grid point; return final metrics + wall time."""
    spec = bench_spec(method, **kw)
    result = api.run(spec, log_fn=lambda *_: None)
    out = {
        "acc": result.final["acc"],
        "acc_std_over_nodes": result.final["acc_std_over_nodes"],
        "loss": result.final["loss"],
        "consensus": result.final["consensus"],
        "us_per_step": result.wall_time_s / max(1, result.steps_run) * 1e6,
        "steps": result.steps_run,
    }
    if "comm_bits_per_node" in result.final:
        out["comm_bits_per_node"] = result.final["comm_bits_per_node"]
        out["comm_ratio"] = result.final["comm_ratio"]
    return out


def bench_loop(method: str = "qg_dsgdm_n", *, alpha: float = 0.1,
               n_nodes: int = 16, steps: int = 128, chunks=(8, 32),
               lr: float = 0.1, seed: int = 0, batch: int = 16) -> list[dict]:
    """Python-loop vs scan-fused training-loop dispatch benchmark.

    Same assembly path as ``run_decentralized`` (``api.build``); each
    variant warms up (one full run compiles every trace, including the tail
    chunk) and then times a fresh `steps`-step run.  The trajectory is
    step-identical across variants (run_training_scanned's contract), so the
    only difference is per-step Python/jit dispatch overhead vs one dispatch
    per chunk.
    """
    from repro.train import run_training, run_training_scanned

    spec = bench_spec(method, alpha=alpha, n_nodes=n_nodes, steps=steps,
                      lr=lr, seed=seed, batch=batch, n_data=2048)
    ex = api.build(spec)
    trainer = ex.trainer

    def fresh():
        # trainer.init is deterministic, so the built init state seeds every
        # variant — but the jitted step DONATES its input state, so each run
        # gets a fresh copy of the buffers; only the batch stream restarts
        return jax.tree.map(jnp.copy, ex.state), ex.task.make_iter()

    variants = [("python", run_training, {})]
    variants += [(f"scan{c}", run_training_scanned, {"chunk": c})
                 for c in chunks]
    rows = []
    base_sps = None
    for tag, runner, kw in variants:
        # warm-up on the SAME trainer: compiles every trace (incl. the tail
        # chunk) so the timed run below measures dispatch, not compilation
        state, batches = fresh()
        runner(trainer, state, batches, steps, log_every=0,
               log_fn=lambda *_: None, **kw)
        state, batches = fresh()
        t0 = time.time()
        state, hist = runner(trainer, state, batches, steps, log_every=0,
                             log_fn=lambda *_: None, **kw)
        jax.block_until_ready(state.params)
        wall = time.time() - t0
        sps = steps / wall
        if base_sps is None:
            base_sps = sps
        rows.append({"tag": tag, "us_per_step": wall / steps * 1e6,
                     "steps_per_s": sps, "speedup": sps / base_sps,
                     "loss": hist[-1]["loss"]})
    return rows


def bench_telemetry(*, n_nodes: int = 8, steps: int = 160, chunk: int = 8,
                    reps: int = 3, every: int = 80) -> list[dict]:
    """Telemetry overhead on the ring-``n_nodes`` scan-fused loop bench:
    steps/s with telemetry off vs cadence-on (every collector, memory sink).

    Cadence is HOST-gated (DESIGN.md §10): a chunk containing an on-cadence
    step runs the telemetry-collecting trace (all ``chunk`` steps collect),
    every other chunk runs the exact telemetry-free graph — so the amortized
    overhead is ~``chunk/every`` of the per-step collector cost, and the
    off-cadence steps are literally free.

    The two variants are warmed up first (all traces compiled), then timed
    in ``reps`` INTERLEAVED rounds taking the best wall time of each — the
    pairing cancels machine-load drift, best-of-N cancels one-off stalls, so
    the CI ≤5% overhead gate on ``overhead_pct`` stays stable.
    """
    from repro.telemetry import MemorySink, TelemetryRecorder
    from repro.train import run_training_scanned

    base = bench_spec("qg_dsgdm_n", alpha=0.1, n_nodes=n_nodes, steps=steps,
                      n_data=2048)
    spec_on = base.replace(telemetry={"enabled": True, "every": every,
                                      "sink": "memory"})
    variants = []
    for tag, spec in (("off", base), ("on", spec_on)):
        ex = api.build(spec)

        def make_run(ex=ex):
            recorder = (None if ex.trainer.telemetry is None else
                        TelemetryRecorder(ex.trainer.telemetry, MemorySink()))

            def go():
                state = jax.tree.map(jnp.copy, ex.state)
                state, hist = run_training_scanned(
                    ex.trainer, state, ex.task.make_iter(), steps,
                    chunk=chunk, log_every=0, log_fn=lambda *_: None,
                    telemetry=recorder)
                jax.block_until_ready(state.params)
                return hist

            return go

        variants.append({"tag": tag, "run": make_run(),
                         "best": float("inf"), "loss": None})

    for v in variants:                 # warm-up: compile every trace
        v["run"]()
    for _ in range(reps):              # interleaved best-of-N timing
        for v in variants:
            t0 = time.time()
            hist = v["run"]()
            v["best"] = min(v["best"], time.time() - t0)
            v["loss"] = hist[-1]["loss"]

    base_sps = steps / variants[0]["best"]
    rows = []
    for v in variants:
        sps = steps / v["best"]
        rows.append({
            "tag": v["tag"], "us_per_step": v["best"] / steps * 1e6,
            "steps_per_s": sps, "loss": v["loss"],
            "overhead_pct": max(0.0, (base_sps / sps - 1.0) * 100.0),
        })
    return rows


ROWS: list[dict] = []  # every csv_row also lands here for --json export


def csv_row(name: str, us: float, derived: str, *,
            platform: str | None = None) -> None:
    """Print one CSV row and keep it for ``--json``.  ``platform`` is the
    device the row was measured on; ``None`` means this process's."""
    print(f"{name},{us:.1f},{derived}")
    if platform is None:
        import jax
        platform = jax.devices()[0].platform
    row = {"name": name, "us_per_call": round(us, 1), "platform": platform}
    for part in derived.split(","):
        k, _, v = part.partition("=")
        if _:
            try:
                row[k] = float(v)
            except ValueError:
                row[k] = v
    ROWS.append(row)
