"""Paper-faithful CV experiment (Table 1 protocol, scaled down): ResNet-20
with EvoNorm-S0 on synthetic CIFAR-shaped data, ring topology, Dirichlet
heterogeneity sweep, DSGDm-N vs QG-DSGDm-N — spec-first: the argparse flags
only parameterize a declarative ``ExperimentSpec`` per grid point, and the
one ``repro.api.run`` path does all the wiring (see also the registered
``cifar_ring16_alpha0.1_qg`` preset).

    PYTHONPATH=src python examples/heterogeneous_cifar.py --steps 60

Compressed gossip (CHOCO behind the mix_fn hook) rides along with
``--compress``, e.g. QG-DSGDm-N at ~2% of full-gossip bandwidth; any other
spec field is reachable with ``--set section.key=value``:

    PYTHONPATH=src python examples/heterogeneous_cifar.py \
        --steps 60 --compress topk:0.01 --set topology.name=exp

``--runtime sharded`` selects the sharded execution backend (DESIGN.md §9):
the whole decentralized step — per-node grads, transform chain, gossip —
runs inside ONE shard_map over a node-axis mesh, each device holding only
its own node's state.  On this CPU container the node "devices" are forced
host devices (set before the first jax import, which is why argument
parsing happens before importing repro); the trajectory is identical to the
default vmap backend.

    PYTHONPATH=src python examples/heterogeneous_cifar.py \
        --steps 20 --nodes 4 --runtime sharded

``--telemetry DIR`` turns on the in-graph telemetry collectors (DESIGN.md
§10) and writes one ``<spec name>.metrics.jsonl`` per grid point into DIR —
consensus distance, momentum/QG-buffer alignment vs the node-mean gradient,
grad-norm spread over nodes, wire bytes, and spectral-gap-normalized mixing
progress.  Render any stream with
``python -m repro.telemetry.report DIR/<name>.metrics.jsonl``.

(ResNet-20 on CPU is slow; defaults are sized for a few minutes.)
"""
import argparse
import os


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--alphas", default="10,0.1")
    ap.add_argument("--norm", default="evonorm", choices=["bn", "gn", "evonorm"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.03)
    ap.add_argument("--runtime", default="auto",
                    choices=["auto", "vmap", "sharded"],
                    help="execution backend (DESIGN.md §9); 'sharded' "
                         "builds an n-node host-device mesh and runs the "
                         "whole step in one shard_map")
    ap.add_argument("--compress", default="",
                    help="gossip compressor spec: topk:<frac> | qsgd:<bits> "
                         "| signnorm | randk:<frac> (default: dense)")
    ap.add_argument("--gamma", type=float, default=None,
                    help="CHOCO consensus step size (default: per-compressor)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="EF14 value exchange instead of CHOCO replicas")
    ap.add_argument("--telemetry", default="", metavar="DIR",
                    help="enable in-graph telemetry (DESIGN.md §10); one "
                         "<spec name>.metrics.jsonl per grid point in DIR")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="dotted spec override, e.g. topology.name=exp")
    return ap.parse_args()


def main():
    args = parse_args()
    if args.runtime == "sharded":
        # a CPU study: one forced host device per node carries the mesh node
        # axis (on chips, use `python chip_smoke.py --chips 4`).  Must
        # precede the first jax import; APPEND so a pre-existing XLA_FLAGS
        # value keeps its other flags
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={args.nodes}"
        if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    import jax
    from repro import api

    mesh = None
    if args.runtime == "sharded":
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(shape=(args.nodes,), axes=("data",))

    if args.compress:
        print(f"compressed gossip: {args.compress} "
              f"(ef={args.error_feedback})")

    for alpha in [float(a) for a in args.alphas.split(",")]:
        for method in ("dsgdm_n", "qg_dsgdm_n"):
            spec = api.ExperimentSpec(
                name=f"cifar_ring{args.nodes}_alpha{alpha}_{method}",
                runtime=args.runtime,
                data=api.DataSpec(dataset="classification", alpha=alpha,
                                  batch=args.batch, n_data=1024,
                                  n_classes=10, hw=16, noise=1.2,
                                  train_frac=0.75),
                topology=api.TopologySpec(name="ring", n=args.nodes),
                optim=api.OptimSpec(name=method, lr=args.lr,
                                    weight_decay=1e-4),
                comm=api.CommSpec(compressor=args.compress or "dense",
                                  gamma=args.gamma,
                                  error_feedback=args.error_feedback),
                loop=api.LoopSpec(steps=args.steps, warmup=5,
                                  decay_at=(0.5, 0.75)),
                model=api.ModelSpec(name="resnet20",
                                    kwargs={"norm": args.norm}),
                telemetry=api.TelemetrySpec(enabled=bool(args.telemetry)),
            ).override(*args.overrides)

            telemetry_path = ""
            if args.telemetry:
                os.makedirs(args.telemetry, exist_ok=True)
                telemetry_path = os.path.join(
                    args.telemetry, f"{spec.name}.metrics.jsonl")
            result = api.run(spec, mesh=mesh, log_fn=lambda *_: None,
                             telemetry_path=telemetry_path)
            bw = (f"  wire={result.wire['ratio_vs_dense']:.0f}x less"
                  if result.wire["ratio_vs_dense"] > 1 else "")
            tm = (f"  telemetry={result.telemetry['path']}"
                  if result.telemetry else "")
            print(f"platform={jax.devices()[0].platform}  "
                  f"alpha={alpha:5.1f}  {method:12s}  "
                  f"test acc={result.final['acc']:.4f}  "
                  f"final loss={result.final['loss']:.3f}  "
                  f"consensus={result.final['consensus']:.2e}{bw}{tm}")


if __name__ == "__main__":
    main()
