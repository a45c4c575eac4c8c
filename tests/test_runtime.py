"""Execution backends (DESIGN.md §9): vmap vs sharded trajectory parity,
one-dispatch-per-chunk, O(1)-per-device state, buffer donation, and the
checkpoint save->resume contract that rides the same runtime surface."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import optim, topology
from repro.runtime import RUNTIMES, ShardedRuntime, VmapRuntime, \
    resolve_runtime
from repro.train import DecentralizedTrainer


def _tiny_task(n=4, d=6, c=5):
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
        for _ in range(steps):
            yield (rng.normal(size=(n, 4, d)).astype(np.float32),
                   rng.integers(0, c, size=(n, 4)))

    return init_fn, loss_fn, batches


# ---------------------------------------------------------------------------
# backend resolution + validation
# ---------------------------------------------------------------------------

def test_resolve_runtime_rules():
    assert RUNTIMES == ("auto", "vmap", "sharded", "hybrid")
    assert resolve_runtime("vmap") == "vmap"
    assert resolve_runtime("sharded") == "sharded"
    assert resolve_runtime("hybrid") == "hybrid"
    assert resolve_runtime("auto") == "vmap"              # no mesh -> vmap
    with pytest.raises(ValueError, match="unknown runtime"):
        resolve_runtime("pmap")


def test_trainer_defaults_to_vmap_without_mesh():
    init_fn, loss_fn, _ = _tiny_task()
    tr = DecentralizedTrainer(loss_fn, optim.make_optimizer("dsgd", lr=0.1),
                              topology.ring(4))
    assert isinstance(tr._runtime, VmapRuntime)


def test_sharded_without_mesh_raises():
    init_fn, loss_fn, _ = _tiny_task()
    with pytest.raises(ValueError, match="sharded.*mesh"):
        DecentralizedTrainer(loss_fn, optim.make_optimizer("dsgd", lr=0.1),
                             topology.ring(4), runtime="sharded")


def test_sharded_mesh_size_mismatch_raises():
    init_fn, loss_fn, _ = _tiny_task()
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1), ("data",))
    with pytest.raises(ValueError, match="size"):
        DecentralizedTrainer(loss_fn, optim.make_optimizer("dsgd", lr=0.1),
                             topology.ring(4), mesh=mesh, runtime="sharded")


def test_spec_runtime_field_validated_and_roundtrips():
    spec = api.ExperimentSpec(runtime="sharded")
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.override("runtime=vmap").runtime == "vmap"
    with pytest.raises(ValueError, match="runtime"):
        api.ExperimentSpec(runtime="bogus").validate()
    with pytest.raises(ValueError, match="checkpoint_every"):
        api.ExperimentSpec(
            loop=api.LoopSpec(checkpoint_every=-1)).validate()


def test_lazy_compilation_no_jit_in_post_init():
    """The __post_init__ eager-jit fix: backends own compilation and build
    the jitted step only on first use."""
    init_fn, loss_fn, batches = _tiny_task()
    tr = DecentralizedTrainer(loss_fn, optim.make_optimizer("dsgd", lr=0.1),
                              topology.ring(4))
    assert tr._runtime._step_fns == {}
    assert tr._runtime._chunk_fns == {}
    st = tr.init(jax.random.PRNGKey(0), init_fn)
    assert tr._runtime._step_fns == {}           # init still doesn't compile
    b = jax.tree.map(jnp.asarray, next(batches(1)))
    tr.step(st, b, jax.random.PRNGKey(1))
    assert set(tr._runtime._step_fns) == {False}  # only the no-collect trace


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------

def test_step_donates_state_buffers():
    """donate_argnums on the jitted step: the incoming TrainState's buffers
    back the output — the old state is freed, and deleting it after the
    step is a no-op rather than a use-after-free."""
    init_fn, loss_fn, batches = _tiny_task()
    tr = DecentralizedTrainer(
        loss_fn, optim.make_optimizer("qg_dsgdm", lr=0.1), topology.ring(4))
    st0 = tr.init(jax.random.PRNGKey(0), init_fn)
    b = jax.tree.map(jnp.asarray, next(batches(1)))
    st1, _ = tr.step(st0, b, jax.random.PRNGKey(1))
    leaf = jax.tree.leaves(st0.params)[0]
    assert leaf.is_deleted()                      # buffer actually freed
    with pytest.raises(RuntimeError):
        _ = leaf + 1                              # old state unusable...
    del st0                                       # ...and delete-after-step
    st2, _ = tr.step(st1, b, jax.random.PRNGKey(2))   # does not raise
    assert not jax.tree.leaves(st2.params)[0].is_deleted()


def test_chunk_donates_state_buffers():
    init_fn, loss_fn, batches = _tiny_task()
    tr = DecentralizedTrainer(
        loss_fn, optim.make_optimizer("dsgdm_n", lr=0.1), topology.ring(4))
    st0 = tr.init(jax.random.PRNGKey(0), init_fn)
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                           *list(batches(3)))
    st1, _, _ = tr.step_chunk(st0, stacked, jax.random.PRNGKey(1))
    assert jax.tree.leaves(st0.params)[0].is_deleted()
    del st0
    jax.block_until_ready(st1.params)


# ---------------------------------------------------------------------------
# checkpoint save -> resume trajectory parity (spec path)
# ---------------------------------------------------------------------------

def _ckpt_spec(steps, chunk=1, every=0):
    return api.ExperimentSpec(
        name="ckpt-test", seed=3,
        data=api.DataSpec(alpha=1.0, batch=8, n_data=256, n_classes=5, hw=4),
        topology=api.TopologySpec(name="ring", n=4),
        optim=api.OptimSpec(name="qg_dsgdm_n", lr=0.05),
        loop=api.LoopSpec(steps=steps, chunk=chunk, log_every=1,
                          checkpoint_every=every),
        eval=api.EvalSpec(enabled=False),
        model=api.ModelSpec(name="mlp"),
    )


@pytest.mark.parametrize("chunk", [1, 4], ids=["python-loop", "scanned"])
def test_save_resume_trajectory_parity(tmp_path, chunk):
    """Interrupt at step 6 of 12, resume from the checkpoint: the combined
    run is step-identical to the uninterrupted one — full TrainState (incl.
    opt/comm state and step counter) AND the rng/batch streams restore."""
    silent = lambda *_: None
    straight, st_straight = api.run(_ckpt_spec(12, chunk), log_fn=silent,
                                    with_state=True)

    path = os.path.join(tmp_path, "ckpt.npz")
    api.run(_ckpt_spec(6, chunk, every=3), log_fn=silent,
            checkpoint_path=path)
    resumed, st_resumed = api.run(_ckpt_spec(12, chunk), log_fn=silent,
                                  resume=path, with_state=True)

    assert int(st_resumed.t) == int(st_straight.t) == 12
    assert resumed.history[0]["step"] >= 6        # absolute indices
    by_step = {h["step"]: h for h in straight.history}
    for h in resumed.history:
        ref = by_step[h["step"]]
        for k in ("loss", "consensus"):
            np.testing.assert_allclose(h[k], ref[k], rtol=2e-4, atol=1e-6,
                                       err_msg=f"{k} @ step {h['step']}")
    for a, b in zip(jax.tree.leaves(st_straight.params),
                    jax.tree.leaves(st_resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_save_resume_restores_comm_state(tmp_path):
    """comm_state (CHOCO replica sites) rides the checkpoint."""
    silent = lambda *_: None
    spec6 = _ckpt_spec(6).replace(comm={"compressor": "topk:0.5"})
    spec12 = _ckpt_spec(12).replace(comm={"compressor": "topk:0.5"})
    path = os.path.join(tmp_path, "ckpt.npz")
    api.run(spec6, log_fn=silent, checkpoint_path=path)
    _, st_resumed = api.run(spec12, log_fn=silent, resume=path,
                            with_state=True)
    _, st_straight = api.run(spec12, log_fn=silent, with_state=True)
    assert st_resumed.comm_state is not None
    for a, b in zip(jax.tree.leaves(st_straight.comm_state),
                    jax.tree.leaves(st_resumed.comm_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("chunk", [1, 4], ids=["python-loop", "scanned"])
def test_hybrid_save_resume_trajectory_parity(tmp_path, chunk):
    """Save->resume parity under runtime=hybrid (the PR-5 tests pinned
    vmap/sharded only): 4 ring nodes as one block on a 1-device node-axis
    mesh — the block runtime's full TrainState (incl. its comm-free
    block-gossip path) restores step-identically."""
    silent = lambda *_: None
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))

    def run(spec, **kw):
        return api.run(spec.replace(runtime="hybrid"), mesh=mesh,
                       log_fn=silent, **kw)

    straight, st_straight = run(_ckpt_spec(12, chunk), with_state=True)
    path = os.path.join(tmp_path, "ckpt.npz")
    run(_ckpt_spec(6, chunk, every=3), checkpoint_path=path)
    resumed, st_resumed = run(_ckpt_spec(12, chunk), resume=path,
                              with_state=True)
    assert int(st_resumed.t) == int(st_straight.t) == 12
    by_step = {h["step"]: h for h in straight.history}
    for h in resumed.history:
        for k in ("loss", "consensus"):
            np.testing.assert_allclose(h[k], by_step[h["step"]][k],
                                       rtol=2e-4, atol=1e-6,
                                       err_msg=f"{k} @ step {h['step']}")
    for a, b in zip(jax.tree.leaves(st_straight.params),
                    jax.tree.leaves(st_resumed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_resume_past_loop_steps_raises(tmp_path):
    silent = lambda *_: None
    path = os.path.join(tmp_path, "ckpt.npz")
    api.run(_ckpt_spec(6), log_fn=silent, checkpoint_path=path)
    with pytest.raises(ValueError, match="loop.steps"):
        api.run(_ckpt_spec(3), log_fn=silent, resume=path)


# ---------------------------------------------------------------------------
# cross-backend trajectory parity (subprocess: forced host devices)
# ---------------------------------------------------------------------------

def _run_sub(script: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=900, env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(__file__)))


_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.comm import make_comm
from repro.core import gossip, optim, topology
from repro.launch.mesh import make_debug_mesh
from repro.runtime import ShardedRuntime
from repro.train import DecentralizedTrainer, run_training, \
    run_training_scanned


def init_fn(key):
    k1, k2 = jax.random.split(key)
    return ({"w": jax.random.normal(k1, (6, 5)) * 0.3,
             "b": jnp.zeros(5)}, {})


def loss_fn(p, ms, batch, rng):
    xb, yb = batch
    logits = xb @ p["w"] + p["b"]
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, yb[:, None].astype(jnp.int32), -1)[:, 0])
    return ce, ({}, {"acc": jnp.mean(jnp.argmax(logits, -1) == yb)})


def batches(n, steps, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(n, 4, 6)).astype(np.float32),
             rng.integers(0, 5, size=(n, 4))) for _ in range(steps)]


def run(topo, mesh, method, comm_spec=None, steps=6):
    tr = DecentralizedTrainer(
        loss_fn, optim.make_optimizer(method, lr=0.1), topo,
        comm=make_comm(comm_spec) if comm_spec else None,
        mesh=mesh, node_axis="data")
    assert isinstance(tr._runtime, ShardedRuntime) == (mesh is not None)
    state = tr.init(jax.random.PRNGKey(0), init_fn)
    state, hist = run_training(tr, state, iter(batches(topo.n, steps)),
                               steps, rng=jax.random.PRNGKey(1),
                               log_every=1, log_fn=lambda *_: None)
    return tr, state, hist


def check(topo, method, comm_spec=None):
    tr_v, st_v, h_v = run(topo, None, method, comm_spec)
    mesh = make_debug_mesh(shape=(topo.n,), axes=("data",))
    tr_s, st_s, h_s = run(topo, mesh, method, comm_spec)
    for hv, hs in zip(h_v, h_s):
        for k in hv:
            np.testing.assert_allclose(hv[k], hs[k], rtol=2e-4, atol=1e-5,
                                       err_msg=f"{method} {k} @ {hv['step']}")
    for a, b in zip(jax.tree.leaves(st_v.params),
                    jax.tree.leaves(st_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    if st_v.comm_state is not None:
        for a, b in zip(jax.tree.leaves(st_v.comm_state),
                        jax.tree.leaves(st_s.comm_state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
    print("PARITY_OK", topo.name, topo.n, method, comm_spec)
    return tr_v, st_v, tr_s, st_s


# >= 4 registry optimizers on ring-8, covering every node-reduction family:
# qg_dsgdm (the paper's core), buffer_sync complete (ctx.n_nodes),
# grad_track (tracker mix site), qg_dadam (per-node norms), slowmo
# (node_mean/pmean + cross-stage reset)
for method in ("qg_dsgdm", "dsgdm_n_sync_global", "mt_dsgdm", "qg_dadam",
               "slowmo"):
    check(topology.ring(8), method)
# CHOCO top-k compressed comm on ring-4 AND ring-8 (ISSUE acceptance), and
# the time-varying 1-peer exp graph (traced-t lax.switch inside the step)
check(topology.ring(4), "qg_dsgdm", "topk:0.5")
tr_v, st_v, tr_s, st_s = check(topology.ring(8), "qg_dsgdm_n", "topk:0.5")
check(topology.one_peer_exponential(8), "qg_dsgdm_n", "topk:0.5")

# evaluate() parity: per-node models on the full eval set, averaged
def eval_fn(p, ms, batch):
    xb, yb = batch
    logits = xb @ p["w"] + p["b"]
    return {"correct": jnp.sum(jnp.argmax(logits, -1) == yb),
            "count": jnp.asarray(float(yb.shape[0]))}

rng = np.random.default_rng(9)
eb = [(jnp.asarray(rng.normal(size=(8, 6)).astype(np.float32)),
       jnp.asarray(rng.integers(0, 5, size=(8,))))]
ev, es = tr_v.evaluate(st_v, eval_fn, eb), tr_s.evaluate(st_s, eval_fn, eb)
assert abs(ev["correct"] - es["correct"]) < 1e-6, (ev, es)
print("EVAL_OK", ev, es)

# chunked path: step-identical AND exactly ONE shard_map entry per chunk
# trace (no per-mix re-entry) — count _shard_map applications while tracing
topo = topology.ring(8)
mesh = make_debug_mesh(shape=(8,), axes=("data",))
bs = batches(8, 8, seed=3)


def run_scanned(mesh):
    tr = DecentralizedTrainer(
        loss_fn, optim.make_optimizer("qg_dsgdm_n", lr=0.1), topo,
        mesh=mesh, node_axis="data")
    st = tr.init(jax.random.PRNGKey(0), init_fn)
    st, hist = run_training_scanned(tr, st, iter(bs), 8, chunk=4,
                                    rng=jax.random.PRNGKey(2), log_every=1,
                                    log_fn=lambda *_: None)
    return st, hist

st_v2, h_v2 = run_scanned(None)
calls = []
orig = gossip._shard_map
gossip._shard_map = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
try:
    st_s2, h_s2 = run_scanned(mesh)
finally:
    gossip._shard_map = orig
assert len(calls) == 1, f"expected ONE shard_map per chunk trace, got " \
    f"{len(calls)} (per-mix re-entry?)"
for hv, hs in zip(h_v2, h_s2):
    np.testing.assert_allclose(hv["loss"], hs["loss"], rtol=2e-4, atol=1e-5)
for a, b in zip(jax.tree.leaves(st_v2.params), jax.tree.leaves(st_s2.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
print("CHUNK_OK one shard_map per chunk")

# O(1) per-device state + donation on the sharded backend
per_dev = {}
for leaf in jax.tree.leaves(st_s2.params):
    for sh in leaf.addressable_shards:
        per_dev[sh.device] = per_dev.get(sh.device, 0) + sh.data.nbytes
total = sum(l.nbytes for l in jax.tree.leaves(st_s2.params))
assert set(per_dev.values()) == {total // 8}, (per_dev, total)
tr = DecentralizedTrainer(loss_fn, optim.make_optimizer("dsgd", lr=0.1),
                          topo, mesh=mesh, node_axis="data")
st0 = tr.init(jax.random.PRNGKey(0), init_fn)
b0 = jax.tree.map(jnp.asarray, bs[0])
st1, _ = tr.step(st0, b0, jax.random.PRNGKey(1))
assert jax.tree.leaves(st0.params)[0].is_deleted()
print("MEM_OK per-device bytes = total/n; sharded donation holds")
print("RUNTIME_PARITY_OK")
"""


def test_cross_backend_trajectory_parity():
    """THE acceptance criterion: ShardedRuntime's trajectory matches
    VmapRuntime's on every pinned scenario — 5 registry optimizers spanning
    every node-reduction family, CHOCO top-k compressed comm on ring-4 and
    ring-8, the time-varying exp graph, evaluate(), the scanned chunk path
    (with exactly ONE shard_map entry per chunk trace), O(1)-in-n per-device
    state bytes, and sharded-side buffer donation (8 forced host devices)."""
    res = _run_sub(_PARITY_SCRIPT)
    assert "RUNTIME_PARITY_OK" in res.stdout, \
        res.stdout[-1500:] + res.stderr[-3000:]


_LM_SHARDED_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from repro import api
from repro.launch.mesh import make_debug_mesh

arch = sys.argv[1]
mesh = make_debug_mesh(shape=(4,), axes=("data",))


def one_step(runtime, schedule, mesh):
    spec = api.ExperimentSpec.from_dict({
        "name": "lm", "seed": 0, "runtime": runtime,
        "data": {"dataset": "lm_domains", "vocab": 512, "alpha": 0.1,
                 "batch": 2, "seq_len": 16, "n_seq_per_domain": 32,
                 "min_per_client": 4},
        "topology": {"name": "ring", "n": 4},
        "gossip": {"schedule": schedule},
        "optim": {"name": "qg_dsgdm_n", "lr": 0.1, "weight_decay": 1e-4,
                  "kwargs": {"beta": 0.9, "mu": 0.9}},
        "loop": {"steps": 1}, "eval": {"enabled": False},
        "model": {"name": "transformer",
                  "kwargs": {"arch": arch, "reduced": True}},
    })
    ex = api.build(spec, mesh=mesh)
    tr = ex.trainer
    batch = tr.put_batch(next(ex.task.make_iter()))
    state, metrics = tr.step(ex.state, batch, jax.random.PRNGKey(0))
    return float(metrics["loss"]), jax.tree.map(np.asarray, state.params)

lv, pv = one_step("vmap", "dense", None)
ls, ps = one_step("sharded", "sparse_ppermute", mesh)
np.testing.assert_allclose(lv, ls, rtol=1e-5)
leaves_v, leaves_s = jax.tree.leaves(pv), jax.tree.leaves(ps)
assert len(leaves_v) == len(leaves_s)
for a, b in zip(leaves_v, leaves_s):
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
print("LM_SHARDED_OK", lv, ls)
"""


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-130m"])
def test_sharded_runtime_lm_matches_vmap(arch):
    """The runtime's sharded step on a reduced language model (ring-4, the
    compiled sparse schedule, fp32, one node per forced host device) gives
    the same loss and parameters after one step as the vmap runtime with
    dense gossip, both built through ``repro.api``."""
    res = subprocess.run(
        [sys.executable, "-c", _LM_SHARDED_SCRIPT, arch],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "LM_SHARDED_OK" in res.stdout, \
        res.stdout[-1500:] + res.stderr[-3000:]


_RECORD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re
import numpy as np
import jax, jax.numpy as jnp
from repro.core import gossip, optim, topology
from repro.launch.mesh import make_debug_mesh
from repro.runtime import ShardedRuntime
from repro.train import DecentralizedTrainer, run_training, \
    run_training_scanned


def init_fn(key):
    return ({"w": jax.random.normal(key, (6, 5)) * 0.3,
             "b": jnp.zeros(5)}, {})


def loss_fn(p, ms, batch, rng):
    xb, yb = batch
    logits = xb @ p["w"] + p["b"]
    ce = jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, yb[:, None].astype(jnp.int32), -1)[:, 0])
    return ce, ({}, {})


rng = np.random.default_rng(0)
bs = [(rng.normal(size=(4, 4, 6)).astype(np.float32),
       rng.integers(0, 5, size=(4, 4))) for _ in range(10)]
mesh = make_debug_mesh(shape=(4,), axes=("data",))
tr = DecentralizedTrainer(
    loss_fn, optim.make_optimizer("qg_dsgdm_n", lr=0.1), topology.ring(4),
    mesh=mesh, node_axis="data")
assert isinstance(tr._runtime, ShardedRuntime)
fresh = lambda: tr.init(jax.random.PRNGKey(0), init_fn)
silent = lambda *_: None

# the compiled step: every all-reduce of more than a scalar (the consensus
# metric's, over every parameter) sits inside the conditional's branch
rt = tr._runtime
hlo = rt._build_step().lower(
    fresh(), jax.tree.map(jnp.asarray, bs[0]), jax.random.PRNGKey(1),
    rt._record_arg(True)).compile().as_text()
wide = []
for line in hlo.splitlines():
    m = re.search(r"= (\(.*?\)|\S+) all-reduce(-start)?\(", line)
    if m and re.search(r"\[\d", m.group(1)):
        wide.append(re.search(r'op_name="([^"]*)"', line).group(1))
assert wide and all("/cond/" in name for name in wide), wide
print("HLO_OK", wide)

# the reference: a direct caller's step records, and its consensus is the
# distance of the state it returns
st, key, ref = fresh(), jax.random.PRNGKey(1), []
for b in bs:
    key, sub = jax.random.split(key)
    st, m = tr.step(st, jax.tree.map(jnp.asarray, b), sub)
    want = float(gossip.consensus_distance(st.params))
    np.testing.assert_allclose(float(m["consensus"]), want, rtol=1e-6)
    ref.append({k: float(v) for k, v in m.items()})

_, h = run_training(tr, fresh(), iter(bs), 7, rng=jax.random.PRNGKey(1),
                    log_every=3, log_fn=silent)
assert [r["step"] for r in h] == [0, 3, 6], h
for r in h:
    np.testing.assert_allclose(r["consensus"], ref[r["step"]]["consensus"],
                               rtol=1e-6)
_, hs = run_training_scanned(tr, fresh(), iter(bs), 7, chunk=4,
                             rng=jax.random.PRNGKey(1), log_every=3,
                             log_fn=silent)
assert [r["step"] for r in hs] == [0, 3, 6], hs
for a, b in zip(h, hs):
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6, err_msg=k)
print("RECORDED_OK")

# inside a recording(False) block the same step leaves the metric out
with tr.recording(False):
    _, m = tr.step(fresh(), jax.tree.map(jnp.asarray, bs[0]),
                   jax.random.PRNGKey(1))
assert np.isnan(float(m["consensus"])) and np.isfinite(float(m["loss"]))
assert tr._record is True

# the iterator runs dry at a chunk boundary (8 batches, the last chunk
# unrecorded) and inside a chunk (6): the last row's consensus is finite
# and that step's own
for n_batches in (8, 6):
    _, he = run_training_scanned(tr, fresh(), iter(bs[:n_batches]), 10,
                                 chunk=4, rng=jax.random.PRNGKey(1),
                                 log_fn=silent)
    last = he[-1]
    assert last["step"] == n_batches - 1, he
    assert np.isfinite(last["consensus"]), he
    np.testing.assert_allclose(last["consensus"],
                               ref[n_batches - 1]["consensus"], rtol=1e-6)
    assert all(np.isfinite(r["consensus"]) for r in he), he
print("EXHAUSTED_OK")
print("RECORD_OK")
"""


def test_consensus_only_on_recorded_steps():
    """The consensus metric is computed only on the steps the loops record
    (sharded runtime, ``qg_dsgdm_n`` on ring-4, 4 forced host devices): its
    all-reduce of every parameter compiles inside a conditional branch; a
    recorded step's value is the distance of the state it returns, in the
    per-step loop and the scanned loop alike; and a run whose iterator runs
    dry still ends on a finite value."""
    res = _run_sub(_RECORD_SCRIPT)
    assert "RECORD_OK" in res.stdout, \
        res.stdout[-1500:] + res.stderr[-3000:]
