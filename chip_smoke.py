"""Smoke run of the main path on one TPU chip (``--chips 4``: on four).

    python chip_smoke.py             # phases A, B, C on one chip
    python chip_smoke.py --chips 4   # the four-chip sharded path only

Everything runs in this one process, through the entry points a user calls
(``repro.api`` specs and ``build``/``run``, the ``repro.serve`` engine).

* A — the paper's cell: ResNet-20/EvoNorm on 32x32x3 inputs, ring-16,
  Dirichlet(0.1), ``qg_dsgdm_n``, ``runtime=vmap``, ``optim.fused=auto``.
  The step must contain the fused Pallas update (``tpu_custom_call``), and
  the same steps with ``fused=off`` must agree with it.
* B — ``mamba2-130m`` at published widths (d_model 768, 24 layers, vocab
  50280) on ``lm_domains`` data, ``qg_dsgdm_n`` on a ring, finite loss.
* C — ``tinyllama-1.1b`` at full width served by ``ServeEngine`` (the
  ``python -m repro.serve --arch tinyllama-1.1b --full`` path); greedy tokens
  must equal ``sequential_generate``'s.
* ``--chips 4`` — ``runtime=sharded``, ring-4, one node per chip: ResNet-20
  against the same n=4 run on ``runtime=vmap`` on one device, each device
  holding a quarter of the params, and ``mamba2-130m`` with a finite loss.

Parity comparisons trace under ``jax.default_matmul_precision("highest")``,
so they compare the code paths and not the TPU's default bf16 matmul passes.
Each phase prints one line; the last line is the JSON device record, printed
only when every phase passed on a TPU.  There is no fallback: no CPU, no
interpret mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# fp32 tolerances of the parity checks (params; losses)
PARAM_RTOL, PARAM_ATOL = 1e-4, 1e-5
LOSS_RTOL = 1e-5
KERNEL_MARKER = "tpu_custom_call"   # a Pallas kernel in the lowered step

RESNET_STEPS = 5
# Phase B size, chosen from ``compiled.memory_analysis()`` of the step
# compiled ahead of time for a v5e (16 GB): with fp32 params, QG buffer and
# grads, 2 nodes at 2 x 512 tokens need 11.8 GB and 4 nodes need 18.9 GB even
# at 1 x 512 (CHANGES.md).
MAMBA_NODES, MAMBA_BATCH, MAMBA_SEQ = 2, 2, 512
MAMBA_DATA_VOCAB = 4096   # lm_domains bigram tables are vocab^2 on the host
MAMBA_STEPS = 3
SERVE_REQUESTS, SERVE_NEW = 8, 16


def _report(name, log, t0, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{name}] {body} {log.summary()} wall_s={time.time() - t0:.1f}",
          flush=True)
    log.reset()


def _assert_close(got, want, what, rtol=PARAM_RTOL, atol=PARAM_ATOL):
    import jax
    import numpy as np
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=what)
        worst = max(worst, float(np.max(np.abs(a - b), initial=0.0)))
    return worst


def _losses(result):
    import numpy as np
    return np.asarray([h["loss"] for h in result.history], np.float64)


def resnet_spec(n: int, runtime: str, fused: str = "auto", *,
                steps: int = RESNET_STEPS, hw: int = 32, batch: int = 32):
    """The ``cifar_ring16_alpha0.1_qg`` preset at CIFAR's 32x32 inputs and
    ``batch`` samples per node, on a ring of ``n``."""
    from repro.api import presets
    return presets.get("cifar_ring16_alpha0.1_qg").override(
        f"topology.n={n}", f"data.hw={hw}", f"data.batch={batch}",
        f"data.n_data={max(1024, 8 * n * batch)}", f"loop.steps={steps}",
        "loop.log_every=1", "eval.enabled=false", f"runtime={runtime}",
        f"optim.fused={fused}")


def mamba_spec(n: int, runtime: str, *, batch: int = MAMBA_BATCH,
               seq: int = MAMBA_SEQ, steps: int = MAMBA_STEPS,
               data_vocab: int = MAMBA_DATA_VOCAB, reduced: bool = False):
    from repro.api.spec import (DataSpec, EvalSpec, ExperimentSpec, LoopSpec,
                                ModelSpec, OptimSpec, TopologySpec)
    return ExperimentSpec(
        name=f"mamba2_130m_ring{n}", seed=0, runtime=runtime,
        data=DataSpec(dataset="lm_domains", alpha=0.1, batch=batch,
                      seq_len=seq, vocab=data_vocab),
        topology=TopologySpec(name="ring", n=n),
        optim=OptimSpec(name="qg_dsgdm_n", lr=0.02, weight_decay=1e-4),
        loop=LoopSpec(steps=steps, log_every=1),
        eval=EvalSpec(enabled=False),
        model=ModelSpec(name="transformer", kwargs={
            "arch": "mamba2-130m", "reduced": reduced})).validate()


def _silent(*_):
    pass


def phase_a(log, **size):
    """ResNet-20 ring-16 on vmap: fused Pallas update vs ``fused=off``."""
    import jax
    import numpy as np
    from repro import api

    t0 = time.time()
    spec = resnet_spec(16, "vmap", "auto", **size)
    ex = api.build(spec)
    batch = ex.trainer.put_batch(next(ex.task.make_iter()))
    hlo = jax.jit(ex.trainer.step).lower(
        ex.state, batch, jax.random.PRNGKey(0)).as_text()
    n_kernels = hlo.count(KERNEL_MARKER)
    if not n_kernels:
        raise AssertionError(f"phase A: no {KERNEL_MARKER} in the step: the "
                             "fused chain fell back to the unfused path")
    del ex
    with jax.default_matmul_precision("highest"):
        fused, st_f = api.run(spec, log_fn=_silent, with_state=True)
        plain, st_p = api.run(spec.override("optim.fused=off"),
                              log_fn=_silent, with_state=True)
    lf, lp = _losses(fused), _losses(plain)
    if lf.size != spec.loop.steps or not np.all(np.isfinite(lf)):
        raise AssertionError(f"phase A: losses {lf}")
    np.testing.assert_allclose(lf, lp, rtol=LOSS_RTOL,
                               err_msg="phase A: fused vs off losses")
    worst = _assert_close(st_f.params, st_p.params,
                          "phase A: fused vs off params")
    _report("A resnet20 ring16 vmap", log, t0,
            batch=tuple(batch[0].shape), steps=spec.loop.steps,
            kernel_calls=n_kernels, loss=_fmt(lf), loss_off=_fmt(lp),
            max_param_diff=f"{worst:.3g}")


def _fmt(xs):
    return "[" + ",".join(f"{float(x):.6f}" for x in xs) + "]"


def _peak_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def phase_b(log, *, n=MAMBA_NODES, **size):
    """mamba2-130m at published widths, decentralized on a ring."""
    import jax
    import numpy as np
    from repro import api
    from repro.api.models import resolve_transformer_config

    t0 = time.time()
    spec = mamba_spec(n, "vmap", **size)
    cfg = resolve_transformer_config(spec.model)
    ex = api.build(spec)
    it = ex.task.make_iter()
    state, rng = ex.state, jax.random.PRNGKey(0)
    n_params = sum(int(np.prod(l.shape[1:]))
                   for l in jax.tree.leaves(state.params))
    batch = ex.trainer.put_batch(next(it))
    compiled = jax.jit(ex.trainer.step, donate_argnums=0).lower(
        state, batch, rng).compile()
    peak = _peak_bytes(compiled)
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit is not None and peak > limit:
        raise AssertionError(f"phase B: step needs {peak} B > {limit} B")
    losses = []
    for i in range(spec.loop.steps):
        rng, sub = jax.random.split(rng)
        if i:
            batch = ex.trainer.put_batch(next(it))
        state, metrics = compiled(state, batch, sub)
        losses.append(float(metrics["loss"]))
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"phase B: non-finite loss {losses}")
    _report("B mamba2-130m ring vmap", log, t0,
            d_model=cfg.d_model, layers=cfg.n_layers, vocab=cfg.vocab_size,
            params_per_node=n_params, nodes=n,
            tokens_per_node=f"{spec.data.batch}x{spec.data.seq_len}",
            step_bytes=peak, device_bytes_limit=limit,
            steps=spec.loop.steps, loss=_fmt(losses))


def phase_c(log, *, arch="tinyllama-1.1b", full=True,
            requests=SERVE_REQUESTS, max_new=SERVE_NEW):
    """``python -m repro.serve --arch tinyllama-1.1b --full``'s engine
    against the sequential dense-cache oracle, greedy tokens."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.serve import ServeEngine, sequential_generate
    from repro.serve.__main__ import make_requests

    t0 = time.time()
    cfg = get_config(arch, reduced=not full)
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    reqs = make_requests(requests, cfg.vocab_size, seed=0, max_new=max_new)
    cache_len = max(len(r.prompt) for r in reqs) + max_new
    with jax.default_matmul_precision("highest"):
        eng = ServeEngine(params, cfg)
        outs = eng.run(reqs)
        bad = []
        for r, o in zip(reqs, outs):
            want = sequential_generate(
                params, cfg, jnp.asarray([r.prompt], jnp.int32),
                gen_len=max_new, cache_len=cache_len)
            if list(o.tokens) != [int(t) for t in want[0, len(r.prompt):]]:
                bad.append(r.id)
    if bad:
        raise AssertionError(f"phase C: engine tokens differ from "
                             f"sequential_generate on requests {bad}")
    _report(f"C {cfg.name} serve", log, t0,
            d_model=cfg.d_model, layers=cfg.n_layers, requests=len(reqs),
            prompt_lens=sorted({len(r.prompt) for r in reqs}),
            new_tokens=sum(len(o.tokens) for o in outs),
            matches=f"{len(reqs) - len(bad)}/{len(reqs)}")


def phase_sharded(log, *, n=4, **size):
    """runtime=sharded ring-4, one node per chip, against vmap on one."""
    import jax
    import numpy as np
    from repro import api
    from repro.launch.mesh import make_debug_mesh

    t0 = time.time()
    mesh = make_debug_mesh((n,), ("data",))
    spec = resnet_spec(n, "sharded", **size.get("resnet", {}))
    with jax.default_matmul_precision("highest"):
        rs, st_s = api.run(spec, mesh=mesh, log_fn=_silent, with_state=True)
        rv, st_v = api.run(spec.override("runtime=vmap"), log_fn=_silent,
                           with_state=True)
    ls, lv = _losses(rs), _losses(rv)
    np.testing.assert_allclose(ls, lv, rtol=LOSS_RTOL,
                               err_msg="sharded vs vmap losses")
    worst = _assert_close(st_s.params, st_v.params,
                          "sharded vs vmap params")
    per_dev = _bytes_per_device(st_s.params)
    total = sum(l.nbytes for l in jax.tree.leaves(st_s.params))
    if len(per_dev) != n or set(per_dev.values()) != {total // n}:
        raise AssertionError(f"sharded params not total/{n} per device: "
                             f"{per_dev} of {total}")
    _report(f"4x resnet20 ring{n} sharded vs vmap", log, t0,
            steps=spec.loop.steps, loss=_fmt(ls), loss_vmap=_fmt(lv),
            max_param_diff=f"{worst:.3g}", param_bytes_total=total,
            param_bytes_per_device=sorted(per_dev.values()))

    t0 = time.time()
    spec = mamba_spec(n, "sharded", **size.get("mamba", {}))
    rm, st_m = api.run(spec, mesh=mesh, log_fn=_silent, with_state=True)
    lm = _losses(rm)
    if lm.size != spec.loop.steps or not np.all(np.isfinite(lm)):
        raise AssertionError(f"mamba sharded: losses {lm}")
    per_dev = _bytes_per_device(st_m.params)
    total = sum(l.nbytes for l in jax.tree.leaves(st_m.params))
    if len(per_dev) != n or set(per_dev.values()) != {total // n}:
        raise AssertionError(f"mamba sharded params not total/{n} per "
                             f"device: {per_dev} of {total}")
    _report(f"4x mamba2-130m ring{n} sharded", log, t0,
            tokens_per_node=f"{spec.data.batch}x{spec.data.seq_len}",
            steps=spec.loop.steps, loss=_fmt(lm),
            param_bytes_per_device=sorted(per_dev.values()))


def _bytes_per_device(tree) -> dict:
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU here (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"[setup] device={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} cache_dir={cache_dir}", flush=True)
    from repro.telemetry import trace
    log = trace.enable().compiles
    if args.chips == 4:
        phase_sharded(log)
    else:
        phase_a(log)
        phase_b(log)
        phase_c(log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
