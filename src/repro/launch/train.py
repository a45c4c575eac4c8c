"""Decentralized training launcher — spec-first.

The CLI flags assemble one declarative ``ExperimentSpec`` (or start from a
registered preset with ``--preset``), and the single ``repro.api.run``
assembly path wires partition + topology + optimizer + comm + gossip
schedule + loop from it.  Any spec field is reachable with
``--set section.key=value`` dotted overrides.

``--reduced`` (the default; CPU-runnable) trains the reduced variant of any
assigned architecture on synthetic non-i.i.d. LM data with the full
decentralized stack (node-stacked params, gossip topology, QG momentum).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --optimizer qg_dsgdm_n --topology ring --nodes 8 \
      --alpha 0.1 --steps 200
  PYTHONPATH=src python -m repro.launch.train \
      --preset lm100m_ring8_alpha0.1_qg --set loop.steps=50
  PYTHONPATH=src python -m repro.launch.train --steps 200 \
      --checkpoint run.npz --checkpoint-every 50     # periodic full state
  PYTHONPATH=src python -m repro.launch.train --steps 200 \
      --checkpoint run.npz --resume run.npz          # continue after a kill
  PYTHONPATH=src python -m repro.launch.train --steps 200 \
      --telemetry metrics.jsonl                      # in-graph telemetry

``--telemetry PATH`` enables the in-graph telemetry collectors (DESIGN.md
§10) — consensus distance, momentum/QG-buffer alignment vs the node-mean
gradient, grad-norm spread, wire bytes, mixing progress — streamed one JSONL
row per step to PATH; render with ``python -m repro.telemetry.report PATH``.
Cadence/collector selection ride the spec: ``--set telemetry.every=10``,
``--set telemetry.metrics='["consensus","alignment"]'``.
"""
from __future__ import annotations

import argparse
import time

from repro import api
from repro.api import presets
from repro.api.models import resolve_transformer_config
from repro.core import topology as topo_lib
from repro.launch.compile_cache import enable_compile_cache


def build_spec(args) -> api.ExperimentSpec:
    """CLI flags -> ExperimentSpec (the historical launcher wiring)."""
    topo_n = topo_lib.get_topology(args.topology, args.nodes).n
    return api.ExperimentSpec(
        name=f"{args.arch}-{args.optimizer}-{args.topology}{topo_n}",
        seed=args.seed,
        data=api.DataSpec(dataset="lm_domains", alpha=args.alpha,
                          batch=args.batch, seq_len=args.seq_len,
                          n_domains=max(4, topo_n)),
        topology=api.TopologySpec(name=args.topology, n=args.nodes),
        optim=api.OptimSpec(name=args.optimizer, lr=args.lr,
                            weight_decay=1e-4),
        loop=api.LoopSpec(steps=args.steps, warmup=args.warmup,
                          decay_at=(0.5, 0.75), log_every=args.log_every,
                          rng_seed=args.seed + 1),
        eval=api.EvalSpec(enabled=False),
        model=api.ModelSpec(name="transformer",
                            kwargs={"arch": args.arch,
                                    "reduced": bool(args.reduced),
                                    "chunk": 256, "ssd_chunk": 64}),
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--optimizer", default="qg_dsgdm_n")
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default="",
                    help="save the FULL TrainState (incl. comm_state + step "
                         "counter) here every loop.checkpoint_every steps "
                         "and at the end")
    ap.add_argument("--resume", default="", metavar="PATH",
                    help="restore a --checkpoint save and continue training "
                         "to loop.steps")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="shorthand for --set loop.checkpoint_every=N")
    ap.add_argument("--telemetry", default="", metavar="PATH",
                    help="enable in-graph telemetry (DESIGN.md §10) and "
                         "stream metrics rows to PATH (.jsonl); shorthand "
                         "for --set telemetry.enabled=true + a sink path")
    ap.add_argument("--preset", default="",
                    help="start from a repro.api preset instead of the flags")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="KEY=VALUE", help="dotted spec override")
    args = ap.parse_args(argv)
    enable_compile_cache()

    spec = presets.get(args.preset) if args.preset else build_spec(args)
    if args.overrides:
        spec = spec.override(*args.overrides)
    if args.checkpoint_every:
        spec = spec.override(
            f"loop.checkpoint_every={args.checkpoint_every}")
    if args.telemetry:
        spec = spec.override("telemetry.enabled=true")

    cfg = resolve_transformer_config(spec.model)
    print(f"arch={cfg.name} params={cfg.n_params():,} "
          f"nodes={spec.topology.n} topology={spec.topology.name} "
          f"optimizer={spec.optim.name} alpha={spec.data.alpha}")
    t0 = time.time()
    result = api.run(spec, checkpoint_path=args.checkpoint,
                     resume=args.resume, telemetry_path=args.telemetry)
    history = result.history
    print(f"done in {time.time()-t0:.1f}s; final loss "
          f"{history[-1]['loss']:.4f} consensus "
          f"{history[-1]['consensus']:.2e}")

    if args.checkpoint:
        print("checkpoint ->", args.checkpoint)
    if result.telemetry and result.telemetry.get("path"):
        print(f"telemetry -> {result.telemetry['path']} "
              f"({result.telemetry['rows_emitted']} rows); render with "
              f"python -m repro.telemetry.report {result.telemetry['path']}")
    return history


if __name__ == "__main__":
    main()
