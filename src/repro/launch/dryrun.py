import os
# a CPU study by design: 512 placeholder host devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder host devices, prove the sharding config is
coherent, and dump roofline artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single            # baseline roofline table (16x16)
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi  # 2x16x16 pass
  ... --gossip sparse_ppermute # compiled collective schedule, any topology
  ... --gossip ring_ppermute   # legacy ring-only schedule (§Perf)

Per combo this compiles:
  full   — the production program (layer scan): proves lowering/compile,
           reports memory_analysis;
  probe1/probe2 — fully-unrolled 1- and 2-period variants whose
           cost_analysis/HLO-collective numbers extrapolate linearly to the
           full depth (see launch/roofline.py).

Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>[__<gossip>].json
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.launch import roofline, sharding, steps
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf


def probe_cfg(cfg, k: int):
    """k periods + the constant tail."""
    return dataclasses.replace(
        cfg, n_layers=len(cfg.period) * k + cfg.tail_layers)


def _mem_summary(compiled) -> str:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return f"<memory_analysis failed: {e}>"
    try:
        return (f"argument={ma.argument_size_in_bytes/1e9:.3f}GB "
                f"output={ma.output_size_in_bytes/1e9:.3f}GB "
                f"temp={ma.temp_size_in_bytes/1e9:.3f}GB "
                f"generated_code={ma.generated_code_size_in_bytes/1e6:.1f}MB")
    except Exception:
        return str(ma)


def _scalar_sharding(mesh):
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def lower_train(sc: steps.StepConfig, mesh, plan, *, compile_full=True):
    pshape = steps.params_shape(sc, node_stacked=True)
    oshape = steps.opt_state_shape(sc, pshape)
    bshape = steps.train_batch_specs(sc)

    pspec = sharding.param_specs(plan, pshape, node_stacked=True,
                                 tie_break_last=sc.shard_tie_break_last)
    ospec = sharding.param_specs(plan, oshape, node_stacked=True,
                                 tie_break_last=sc.shard_tie_break_last)
    bspec = sharding.batch_specs(plan, bshape)

    node_axis = plan.node_axis
    fn = steps.build_train_step(sc, mesh=mesh, node_axis=node_axis)
    with mesh:
        jitted = jax.jit(
            fn,
            in_shardings=(sharding.named(plan, pspec),
                          sharding.named(plan, ospec),
                          sharding.named(plan, bspec)),
            out_shardings=(sharding.named(plan, pspec),
                           sharding.named(plan, ospec),
                           _scalar_sharding(mesh)),
        )
        lowered = jitted.lower(pshape, oshape, bshape)
        compiled = lowered.compile()
    return compiled


def lower_prefill(sc: steps.StepConfig, mesh, plan):
    pshape = steps.params_shape(sc, node_stacked=False)
    pspec = sharding.param_specs(plan, pshape, node_stacked=False,
                                 tie_break_last=sc.shard_tie_break_last)
    ispecs = steps.prefill_specs(sc)
    bspec = sharding.batch_specs(plan, ispecs)
    fn = steps.build_prefill_step(sc, mesh=mesh)
    with mesh:
        jitted = jax.jit(
            fn,
            in_shardings=(sharding.named(plan, pspec),
                          sharding.named(plan, bspec["tokens"]),
                          sharding.named(plan, bspec["img"])
                          if "img" in ispecs else None),
        )
        args = (pshape, ispecs["tokens"], ispecs.get("img"))
        if "img" not in ispecs:
            jitted = jax.jit(
                fn, in_shardings=(sharding.named(plan, pspec),
                                  sharding.named(plan, bspec["tokens"])))
            args = (pshape, ispecs["tokens"])
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def lower_decode(sc: steps.StepConfig, mesh, plan):
    pshape = steps.params_shape(sc, node_stacked=False)
    pspec = sharding.param_specs(plan, pshape, node_stacked=False,
                                 tie_break_last=sc.shard_tie_break_last)
    dspecs = steps.decode_specs(sc)
    tok_spec = sharding.batch_specs(plan, dspecs["token"])
    cache_spec = sharding.cache_specs(plan, dspecs["cache"],
                                      shard_features=sc.cache_shard_features)
    constraint = None
    if sc.pin_decode_cache:
        # pin the per-layer-slice KV layout (drop the stacked layer axis)
        from jax.sharding import NamedSharding, PartitionSpec as P
        flat, _ = jax.tree_util.tree_flatten_with_path(cache_spec)
        for kp, spec in flat:
            keys = [getattr(pp, "key", getattr(pp, "idx", None)) for pp in kp]
            if keys and keys[-1] == "k" and "blocks" in keys:
                constraint = NamedSharding(mesh, P(*spec[1:]))
                break
    fn = steps.build_decode_step(sc, cache_constraint=constraint)
    with mesh:
        jitted = jax.jit(
            fn,
            in_shardings=(sharding.named(plan, pspec),
                          sharding.named(plan, tok_spec),
                          _scalar_sharding(mesh),
                          sharding.named(plan, cache_spec)),
        )
        lowered = jitted.lower(pshape, dspecs["token"], dspecs["pos"],
                               dspecs["cache"])
        compiled = lowered.compile()
    return compiled


def run_combo(arch: str, shape_name: str, mesh_name: str, *,
              gossip_schedule: str = "dense", out_dir: str,
              skip_existing: bool = True, probes_only: bool = False,
              full_only: bool = False, variant: str = "",
              overrides: dict | None = None) -> dict | None:
    """``variant``/``overrides`` implement §Perf hillclimb runs: overrides
    are extra StepConfig fields; the artifact gets a ``__<variant>`` suffix."""
    overrides = overrides or {}
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]

    if shape.name == "long_500k" and not cfg.supports_long_context:
        return None  # documented skip (DESIGN.md §5)

    suffix = "" if gossip_schedule == "dense" else f"__{gossip_schedule}"
    if variant:
        suffix += f"__{variant}"
    tag = f"{arch}__{shape_name}__{mesh_name}{suffix}"
    out_path = os.path.join(out_dir, tag + ".json")
    if skip_existing and os.path.exists(out_path):
        return json.load(open(out_path))

    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = mesh.devices.size

    if shape.kind == "train":
        n_nodes = steps.choose_n_nodes(cfg, mesh)
    else:
        n_nodes = 1
    plan = sharding.make_plan(mesh, n_nodes=n_nodes)

    lower_fn = {"train": lower_train, "prefill": lower_prefill,
                "decode": lower_decode}[shape.kind]

    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_chips": int(n_chips), "n_nodes": int(n_nodes),
        "node_axis": plan.node_axis, "kind": shape.kind,
        "gossip": gossip_schedule if shape.kind == "train" else None,
        "variant": variant or "baseline",
        "overrides": {k: str(v) for k, v in overrides.items()},
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    # SSD chunking: keep the number of UNROLLED probe chunk-bodies bounded
    # (len(period) periods x 2 x S/chunk <= ~256) so probe compiles stay
    # tractable on one host core; zamba2 prefill_32k gets chunk 2048 instead
    # of 256 (distortion documented in EXPERIMENTS.md §Methodology).
    ssd_chunk = int(overrides.pop("ssd_chunk", 256))
    if shape.kind != "decode" and cfg.ssm is not None \
            and "ssd_chunk" not in record["overrides"]:
        import math
        need = len(cfg.period) * 2 * shape.seq_len / 256
        if need > 256:
            ssd_chunk = 1 << math.ceil(math.log2(
                len(cfg.period) * 2 * shape.seq_len / 256))
    record["ssd_chunk"] = ssd_chunk

    t0 = time.time()
    mem = "<skipped>"
    if not probes_only:
        sc_full = steps.StepConfig(cfg=cfg, shape=shape, n_nodes=n_nodes,
                                   ssd_chunk=ssd_chunk,
                                   gossip_schedule=gossip_schedule,
                                   **overrides)
        compiled_full = lower_fn(sc_full, mesh, plan)
        mem = _mem_summary(compiled_full)
        record["full_compile_s"] = round(time.time() - t0, 1)
        del compiled_full
    record["memory_analysis"] = mem

    if not full_only:
        pcosts = []
        for k in (1, 2):
            t1 = time.time()
            cfg_k = probe_cfg(cfg, k)
            sc_k = steps.StepConfig(cfg=cfg_k, shape=shape, n_nodes=n_nodes,
                                    unroll=True, ssd_chunk=ssd_chunk,
                                    gossip_schedule=gossip_schedule,
                                    **overrides)
            compiled_k = lower_fn(sc_k, mesh, plan)
            pcosts.append(roofline.ProbeCost.from_compiled(compiled_k))
            record[f"probe{k}_compile_s"] = round(time.time() - t1, 1)
            del compiled_k
        summary = roofline.summarize(
            cfg, shape, n_chips=n_chips, probe1=pcosts[0], probe2=pcosts[1],
            n_periods=cfg.n_periods, memory_analysis=mem,
            extra={"probe1": dataclasses.asdict(pcosts[0]),
                   "probe2": dataclasses.asdict(pcosts[1])})
        record.update({k: v for k, v in summary.items()
                       if k not in ("arch", "shape", "memory_analysis")})

    record["platform"] = jax.devices()[0].platform
    os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--gossip", default="dense",
                    choices=["dense", "ring_ppermute", "sparse_ppermute"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--probes-only", action="store_true")
    ap.add_argument("--full-only", action="store_true")
    ap.add_argument("--variant", default="",
                    help="hillclimb tag; combine with --set key=value")
    ap.add_argument("--set", action="append", default=[],
                    help="StepConfig override, e.g. --set ssd_chunk=64")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            v = {"true": True, "false": False}.get(v.lower(), v)
        overrides[k] = v

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = (list(INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                tag = f"{arch} x {shape_name} x {mesh_name}"
                try:
                    t0 = time.time()
                    rec = run_combo(
                        arch, shape_name, mesh_name,
                        gossip_schedule=args.gossip, out_dir=args.out,
                        skip_existing=not args.force,
                        probes_only=args.probes_only,
                        full_only=args.full_only, variant=args.variant,
                        overrides=overrides)
                    if rec is None:
                        print(f"[skip] {tag} (long-context not supported)")
                        continue
                    rt = rec.get("roofline", {})
                    print(f"[ok]   {tag}  {time.time()-t0:.0f}s  "
                          f"bottleneck={rt.get('bottleneck','-')}  "
                          f"mem: {rec.get('memory_analysis','')[:80]}")
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e)
        sys.exit(1)
    print("\nall requested combos lowered + compiled OK")


if __name__ == "__main__":
    main()
