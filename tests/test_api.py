"""Declarative experiment API (repro.api): spec round-trip, eager
validation, preset smoke runs, and the bit-for-bit pin against the
pre-refactor hand-wired quickstart — plus the satellite fixes that rode
along (make_comm spec rejection, scanned-loop exhaustion warning, the
gossip resolver)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import presets

# ---------------------------------------------------------------------------
# serialization round-trip + overrides
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", presets.names())
def test_preset_roundtrip(name):
    s = presets.get(name)          # .get() validates
    assert api.ExperimentSpec.from_dict(s.to_dict()) == s
    assert api.ExperimentSpec.from_json(s.to_json()) == s
    # to_dict is JSON-plain all the way down
    json.dumps(s.to_dict())


def test_overrides_dotted():
    s = presets.get("quickstart_ring16_alpha0.1_qg").override(
        "loop.steps=3", "data.alpha=0.5", "comm.compressor=topk:0.01",
        "loop.decay_at=[0.5, 0.75]", "topology.name=exp")
    assert s.loop.steps == 3 and s.data.alpha == 0.5
    assert s.comm.compressor == "topk:0.01"      # bare string survives
    assert s.loop.decay_at == (0.5, 0.75)        # JSON list -> tuple
    assert s.topology.name == "exp"


def test_overrides_unknown_path():
    s = presets.get("quickstart_ring16_alpha0.1_qg")
    with pytest.raises(ValueError, match="valid keys"):
        s.override("loop.stepz=3")
    with pytest.raises(ValueError, match="section.key=value"):
        s.override("loop.steps")


def test_from_dict_rejects_unknown_keys():
    d = presets.get("quickstart_ring16_alpha0.1_qg").to_dict()
    d["loop"]["bogus"] = 1
    with pytest.raises(ValueError, match="unknown keys"):
        api.ExperimentSpec.from_dict(d)


# ---------------------------------------------------------------------------
# eager cross-field validation
# ---------------------------------------------------------------------------


def _base(**kw):
    return presets.get("quickstart_ring16_alpha0.1_qg").replace(**kw)


@pytest.mark.parametrize("updates,match", [
    ({"topology": {"name": "social", "n": 16}}, "fixed n=32"),
    ({"topology": {"name": "exp", "n": 12}}, "power-of-two"),
    ({"topology": {"name": "hypercube"}}, "unknown topology"),
    # the retired legacy ring schedule: a saved spec carrying it fails loudly
    pytest.param({"gossip": {"schedule": "ring_ppermute"},
                  "topology": {"name": "exp", "n": 16}},
                 "unknown schedule 'ring_ppermute'",
                 id="updates3-ring_ppermute"),
    ({"gossip": {"schedule": "warp"}}, "unknown schedule"),
    ({"data": {"n_data": 64, "min_per_client": 4}}, "unsatisfiable"),
    ({"data": {"alpha": 0.0}}, "alpha must be > 0"),
    ({"optim": {"name": "adamw"}}, "unknown optimizer"),
    ({"optim": {"stages": (("warpdrive", {}),)}}, "unknown stage"),
    ({"comm": {"compressor": "topk:"}}, "valid forms"),
    ({"comm": {"gamma": 1.5}}, "gamma"),
    ({"model": {"name": "cnn9000"}}, "unknown model plugin"),
    ({"data": {"dataset": "lm_domains", "vocab": 512}},
     "consumes classification"),
    ({"model": {"name": "transformer"}}, "consumes lm_domains"),
    ({"loop": {"steps": 0}}, "steps"),
])
def test_validation_errors(updates, match):
    with pytest.raises(ValueError, match=match):
        _base(**updates).validate()


# ---------------------------------------------------------------------------
# the pin: spec-built quickstart == pre-refactor hand wiring, bit for bit
# ---------------------------------------------------------------------------


def _hand_wired_quickstart(method: str, steps: int):
    """The exact pre-refactor examples/quickstart.py wiring."""
    from repro.core import optim, topology
    from repro.data import (ClientDataset, dirichlet_partition,
                            make_classification)
    from repro.train import DecentralizedTrainer, run_training

    x, y = make_classification(n=4096, hw=8, n_classes=20, noise=2.5, seed=0)
    x = x.reshape(len(x), -1)
    parts = dirichlet_partition(y[:2048], n_clients=16, alpha=0.1, seed=0)
    ds = ClientDataset((x[:2048], y[:2048]), parts, batch=16)

    def init_fn(key):
        k1, k2 = jax.random.split(key)
        return ({"w1": jax.random.normal(k1, (x.shape[1], 64)) * 0.05,
                 "b1": jnp.zeros(64),
                 "w2": jax.random.normal(k2, (64, 20)) * 0.1,
                 "b2": jnp.zeros(20)}, {})

    def loss_fn(p, _state, batch, _rng):
        xb, yb = batch
        logits = jax.nn.relu(xb @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        yb = yb.astype(jnp.int32)
        ce = jnp.mean(jax.nn.logsumexp(logits, -1)
                      - jnp.take_along_axis(logits, yb[:, None], -1)[:, 0])
        return ce, ({}, {})

    trainer = DecentralizedTrainer(
        loss_fn, optim.make_optimizer(method, lr=0.1, weight_decay=1e-4),
        topology.ring(16))
    state = trainer.init(jax.random.PRNGKey(0), init_fn)
    state, hist = run_training(
        trainer, state, iter(lambda: ds.next_batch(), None), steps,
        log_every=1, log_fn=lambda *_: None)

    def acc(p):
        logits = jax.nn.relu(jnp.asarray(x[2048:]) @ p["w1"] + p["b1"]) \
            @ p["w2"] + p["b2"]
        return jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(y[2048:]))

    return hist, float(jnp.mean(jax.vmap(acc)(state.params)))


@pytest.mark.parametrize("preset,method", [
    ("quickstart_ring16_alpha0.1_dsgdm", "dsgdm_n"),
    ("quickstart_ring16_alpha0.1_qg", "qg_dsgdm_n"),
])
def test_quickstart_pinned_bit_for_bit(preset, method):
    steps = 3
    hist_ref, acc_ref = _hand_wired_quickstart(method, steps)
    spec = presets.get(preset).override(
        f"loop.steps={steps}", "loop.chunk=1", "loop.log_every=1")
    res = api.run(spec, log_fn=lambda *_: None)
    assert [h["step"] for h in res.history] == [h["step"] for h in hist_ref]
    for hr, hh in zip(res.history, hist_ref):
        for k in ("loss", "consensus", "grad_norm", "lr"):
            assert hr[k] == hh[k], (k, hr[k], hh[k])   # EXACT, not approx
    assert res.final["acc"] == acc_ref


# ---------------------------------------------------------------------------
# 3-step smoke per preset (scaled down via overrides where heavy)
# ---------------------------------------------------------------------------

_TINY_LM = {"kwargs": {
    "arch": "tinyllama-1.1b",
    "overrides": {"name": "llama-tiny", "n_layers": 1, "d_model": 64,
                  "n_heads": 2, "n_kv_heads": 2, "head_dim": 32,
                  "d_ff": 128, "vocab_size": 256},
    "chunk": 16}}


def _smoke_spec(name):
    s = presets.get(name).override("loop.steps=3", "loop.chunk=1",
                                   "loop.log_every=0")
    if s.model.name == "mlp":
        s = s.replace(data={"n_data": 512})
        if s.topology.n > 64:     # thousand-node presets: smoke at n=64
            s = s.replace(topology={"n": 64})
    elif s.model.name == "resnet20":
        s = s.replace(data={"n_data": 256, "batch": 4},
                      topology={"n": 4})
    elif s.model.name == "transformer":
        s = s.replace(model=_TINY_LM, topology={"n": 4},
                      data={"seq_len": 16, "batch": 2})
    return s


@pytest.mark.parametrize("name", presets.names())
def test_run_smoke_per_preset(name):
    res = api.run(_smoke_spec(name), log_fn=lambda *_: None)
    assert res.steps_run == 3
    assert len(res.history) >= 1 and np.isfinite(res.history[-1]["loss"])
    assert res.wire["bits_per_node_per_step"] > 0
    if "topk" in name or "signnorm" in name:
        assert res.wire["ratio_vs_dense"] > 1.0
    json.dumps(res.to_dict())       # Result is JSON-dumpable as promised


def test_explicit_stage_chain_matches_registry():
    stages = (("weight_decay", {"wd": 1e-4}),
              ("heavyball", {"beta": 0.9, "seed_from": "qg_buffer"}),
              ("gossip_mix", {}),
              ("qg_buffer", {"mu": 0.9}))
    base = _smoke_spec("quickstart_ring16_alpha0.1_qg")
    chain = base.replace(optim={"name": "qg_dsgdm_n", "stages": stages})
    named = base.replace(optim={"name": "qg_dsgdm", "kwargs": {"mu": 0.9},
                                "stages": ()})
    r1 = api.run(chain, log_fn=lambda *_: None)
    r2 = api.run(named, log_fn=lambda *_: None)
    assert r1.history[-1]["loss"] == r2.history[-1]["loss"]
    assert api.ExperimentSpec.from_json(chain.to_json()) == chain


def test_build_exposes_experiment_parts():
    ex = api.build(_smoke_spec("quickstart_ring16_alpha0.1_qg"))
    assert ex.trainer.topology.n == 16
    assert ex.task.n_classes == 20 and ex.task.d_in == 8 * 8 * 3
    batch = next(ex.task.make_iter())
    assert batch[0].shape[:2] == (16, 16)
    assert ex.eval_fn is not None


# ---------------------------------------------------------------------------
# satellite: make_comm / make_compressor malformed-spec rejection
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    "topk:", "qsgd:0", "qsgd:17", "qsgd:half", "topk:1.5", "topk:0",
    "topk:nope", "randk:-1", "signnorm:3", "dense:1", "bogus", "bogus:1",
])
def test_make_comm_rejects_malformed(bad):
    from repro.comm import make_comm
    with pytest.raises(ValueError, match="valid forms"):
        make_comm(bad)


def test_make_comm_gamma_range():
    from repro.comm import make_comm
    with pytest.raises(ValueError, match="gamma"):
        make_comm("topk:0.1", gamma=0.0)
    assert make_comm("topk:0.1", gamma=1.0) is not None


def test_make_comm_good_specs_still_parse():
    from repro.comm import make_comm
    assert make_comm("dense") is None and make_comm("") is None
    assert make_comm("topk:0.02").compressor.frac == 0.02
    assert make_comm("qsgd:6").compressor.bits == 6
    assert make_comm("randk").compressor.frac == 0.05   # default arg form
    assert make_comm("signnorm") is not None


# ---------------------------------------------------------------------------
# satellite: scanned loop warns + records honestly on iterator exhaustion
# ---------------------------------------------------------------------------


def test_scanned_exhaustion_warns_and_truncates():
    from repro.core import optim, topology
    from repro.train import DecentralizedTrainer, run_training_scanned

    n, d = 4, 8

    def init_fn(key):
        return {"w": jax.random.normal(key, (d,))}, {}

    def loss_fn(p, _s, b, _r):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2), ({}, {})

    tr = DecentralizedTrainer(loss_fn, optim.make_optimizer("dsgd", lr=0.01),
                              topology.ring(n))
    st = tr.init(jax.random.PRNGKey(0), init_fn)
    rng = np.random.default_rng(0)
    batches = [(rng.normal(size=(n, 2, d)).astype(np.float32),
                rng.normal(size=(n, 2)).astype(np.float32))
               for _ in range(7)]                       # 7 < 10 requested
    logs = []
    st, hist = run_training_scanned(tr, st, iter(batches), 10, chunk=4,
                                    log_every=0, log_fn=logs.append)
    assert int(st.t) == 7                                # ran what it had
    assert hist[-1]["step"] == 6                         # last REAL step
    assert any("exhausted after 7 steps" in str(m) for m in logs)

    # exhaustion at an EXACT chunk boundary (8 batches, chunk=4) is only
    # discovered on the next chunk's first next(); the last executed step
    # must still land in the history
    st2 = tr.init(jax.random.PRNGKey(0), init_fn)
    logs2 = []
    st2, hist2 = run_training_scanned(
        tr, st2, iter(batches + batches[:1]), 12, chunk=4,
        log_every=0, log_fn=logs2.append)
    assert int(st2.t) == 8
    assert hist2 and hist2[-1]["step"] == 7
    assert any("exhausted after 8 steps" in str(m) for m in logs2)


# ---------------------------------------------------------------------------
# the gossip resolver
# ---------------------------------------------------------------------------


def test_resolve_gossip_rules():
    from repro.core import gossip, topology

    ring4 = topology.ring(4)
    # dense everywhere without a mesh; n=1 always dense
    assert gossip.resolve_gossip(ring4).kind == "dense"
    assert gossip.resolve_gossip(topology.ring(1),
                                 schedule="sparse_ppermute").kind == "dense"
    with pytest.raises(ValueError, match="needs mesh"):
        gossip.resolve_gossip(ring4, schedule="sparse_ppermute")
    with pytest.raises(ValueError, match="unknown gossip schedule"):
        gossip.resolve_gossip(ring4, schedule="warp")
    mesh1 = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1), ("data",))
    with pytest.raises(ValueError, match="has size 1, topology"):
        gossip.resolve_gossip(ring4, schedule="sparse_ppermute", mesh=mesh1,
                              node_axis="data")
    with pytest.raises(ValueError, match="no axis 'nodes'"):
        gossip.resolve_gossip(ring4, schedule="sparse_ppermute", mesh=mesh1,
                              node_axis="nodes")


def test_trainer_refuses_ring_ppermute():
    """The retired legacy ring schedule is an unknown name to the trainer on
    every runtime: the compiled schedule covers a ring in two rounds."""
    from repro.core import optim, topology
    from repro.launch.mesh import make_debug_mesh
    from repro.train import DecentralizedTrainer

    def loss_fn(p, _s, b, _r):
        return jnp.mean((b[0] @ p["w"] - b[1]) ** 2), ({}, {})

    mesh1 = make_debug_mesh((1,), ("data",))
    for runtime, mesh in (("vmap", None), ("hybrid", mesh1)):
        with pytest.raises(ValueError,
                           match="unknown gossip schedule 'ring_ppermute'"):
            DecentralizedTrainer(
                loss_fn, optim.make_optimizer("dsgd", lr=0.01),
                topology.ring(4), mesh=mesh, runtime=runtime,
                gossip_schedule="ring_ppermute")
