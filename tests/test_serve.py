"""Consensus serving stack (DESIGN.md §13): export, paged KV cache,
continuous-batching engine, kernels, and CLI flags.

Parity contracts pinned here:
* consensus export == mean over the node axis, bit-for-bit;
* paged decode logits == dense-cache ``decode_step`` (page-size sweep,
  non-divisible lengths, slot reuse after eviction — no zeroing);
* engine greedy tokens == sequential dense-cache baseline, request-exact;
* ``launch.serve.generate`` == the pre-engine implementation (the old
  ``if i == gen_len - 1: break`` loop), token-for-token.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, serve
from repro.api.spec import (DataSpec, EvalSpec, ExperimentSpec, LoopSpec,
                            ModelSpec, OptimSpec, TopologySpec)
from repro.configs import get_config
from repro.kernels import ops as kops
from repro.kernels.ref import paged_decode_attention_ref
from repro.launch import serve as launch_serve
from repro.models import moe as moe_lib
from repro.models import transformer as tf
from repro.serve.__main__ import make_requests

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    return tf.init_lm(KEY, cfg), cfg


@pytest.fixture(scope="module")
def ring8_run():
    """A real ring-8 QG-DSGDm-N run (the paper's regime, smoke-sized)."""
    spec = ExperimentSpec(
        name="serve_export_test", seed=0,
        data=DataSpec(dataset="lm_domains", alpha=0.1, batch=2, seq_len=32),
        topology=TopologySpec(name="ring", n=8),
        optim=OptimSpec(name="qg_dsgdm_n", lr=0.02),
        loop=LoopSpec(steps=2, chunk=1, log_every=0),
        eval=EvalSpec(enabled=False),
        model=ModelSpec(name="transformer",
                        kwargs={"arch": "tinyllama-1.1b", "reduced": True}))
    return api.run(spec, with_state=True, log_fn=lambda *_: None)


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def test_consensus_is_mean_over_node_axis(ring8_run):
    result, state = ring8_run
    params, cfg = serve.export_consensus(result, state=state)
    want = jax.tree.map(lambda l: jnp.mean(l, axis=0), state.params)
    for got, exp in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert got.shape == exp.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    assert cfg is not None and cfg.name == "tinyllama-1.1b-reduced"
    # nodes have genuinely diverged (heterogeneous data): consensus is a
    # real average, not a copy of node 0
    leaf = jax.tree.leaves(state.params)[0]
    assert float(jnp.max(jnp.abs(leaf[0] - leaf[1]))) > 0


def test_serving_checkpoint_roundtrip(ring8_run, tmp_path):
    result, state = ring8_run
    params, cfg = serve.export_consensus(result, state=state)
    path = str(tmp_path / "model.npz")
    serve.save_serving_checkpoint(path, params, cfg)
    p2, c2 = serve.load_serving_checkpoint(path)
    assert c2 == cfg and isinstance(c2.period, tuple)
    assert (jax.tree_util.tree_structure(p2)
            == jax.tree_util.tree_structure(params))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="not a serving checkpoint"):
        np.savez(tmp_path / "bad.npz", __meta__="{}")
        serve.load_serving_checkpoint(str(tmp_path / "bad.npz"))


def test_config_dict_roundtrip_moe():
    cfg = get_config("granite-moe-3b-a800m", reduced=True)
    back = serve.config_from_dict(serve.config_to_dict(cfg))
    assert back == cfg and back.moe.n_experts == cfg.moe.n_experts


def test_export_from_train_checkpoint(ring8_run, tmp_path):
    from repro.train.checkpoint import save_train_state
    result, state = ring8_run
    path = str(tmp_path / "train.npz")
    save_train_state(path, state, rng=jax.random.PRNGKey(0))
    stacked = serve.params_from_train_checkpoint(path)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    params, cfg = serve.export_consensus(path, spec=result.spec)
    want, _ = serve.export_consensus(result, state=state)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert cfg is not None


# ---------------------------------------------------------------------------
# paged KV cache accounting
# ---------------------------------------------------------------------------

def test_kvcache_reservation_accounting(tiny):
    _, cfg = tiny
    kv = serve.PagedKVCache(cfg, n_slots=2, n_pages=6, page_size=8,
                            max_len=32)
    assert kv.pages_needed(17) == 3
    kv.admit(0, 24)                       # reserves 3 pages, holds 0
    assert kv.outstanding() == 3 and kv.can_admit(24)
    assert not kv.can_admit(25)           # 6 free - 3 outstanding < 4
    with pytest.raises(RuntimeError, match="already active"):
        kv.admit(0, 8)
    kv.ensure(0, 17)                      # lazily allocates 3 pages
    assert kv.held(0) == 3 and kv.outstanding() == 0
    with pytest.raises(RuntimeError, match="exceed max_len"):
        kv.ensure(0, 33)
    kv.release(0)
    assert kv.free_pages() == 6 and kv.held(0) == 0
    assert kv.peak_pages_used == 3


# ---------------------------------------------------------------------------
# paged step vs dense-cache oracle
# ---------------------------------------------------------------------------

def _dense_reference(params, cfg, prompt, gen):
    """Greedy dense-cache decode: returns per-step logits [gen+1, Vp]."""
    l, cache = tf.prefill(params, prompt[None, :], cfg,
                          cache_len=prompt.shape[0] + gen)
    logs = [l[0]]
    tok = jnp.argmax(l, axis=-1)[:, None]
    for i in range(gen):
        l, cache = tf.decode_step(params, tok,
                                  jnp.asarray(prompt.shape[0] + i,
                                              jnp.int32), cache, cfg)
        logs.append(l[0])
        tok = jnp.argmax(l, axis=-1)[:, None]
    return jnp.stack(logs)


@pytest.mark.parametrize("arch,ps,length,gen", [
    ("tinyllama-1.1b", 64, 12, 4),     # one page covers everything
    ("tinyllama-1.1b", 8, 13, 6),      # non-divisible prompt + growth
    ("gemma2-27b", 8, 13, 6),          # local/global windows + softcaps
    ("granite-moe-3b-a800m", 16, 16, 4),  # MoE (chunk == prompt len)
])
def test_paged_matches_dense(arch, ps, length, gen):
    cfg = get_config(arch, reduced=True)
    params = tf.init_lm(KEY, cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(7), (length,), 0,
                                cfg.vocab_size)
    want = _dense_reference(params, cfg, prompt, gen)

    kv = serve.PagedKVCache(cfg, n_slots=1, n_pages=12, page_size=ps,
                            max_len=max(ps, length + gen))
    step = jax.jit(functools.partial(tf.paged_step, cfg=cfg, page_size=ps))
    kv.admit(0, length + gen)
    kv.ensure(0, length)
    # full-prompt chunk (C == L keeps MoE capacity aligned with the dense
    # prefill — capacity is a function of the physical token count)
    logits, kv.pages = step(params, prompt[None, :],
                            jnp.zeros((1,), jnp.int32),
                            jnp.asarray([length], jnp.int32),
                            kv.device_tables(), kv.pages)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want[0]),
                               atol=2e-4, rtol=2e-4)
    tok = int(jnp.argmax(logits[0]))
    assert tok == int(jnp.argmax(want[0]))
    for i in range(gen):
        kv.ensure(0, length + i + 1)
        logits, kv.pages = step(params, jnp.asarray([[tok]], jnp.int32),
                                jnp.asarray([length + i], jnp.int32),
                                jnp.ones((1,), jnp.int32),
                                kv.device_tables(), kv.pages)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(want[i + 1]),
                                   atol=2e-4, rtol=2e-4)
        tok = int(jnp.argmax(logits[0]))
        assert tok == int(jnp.argmax(want[i + 1]))


def test_paged_slot_reuse_after_eviction(tiny):
    """Release slot 0, admit a different sequence into the SAME pages
    (never zeroed) — logits must match a dense run of the new sequence."""
    params, cfg = tiny
    ps, gen = 8, 4
    kv = serve.PagedKVCache(cfg, n_slots=1, n_pages=4, page_size=ps,
                            max_len=32)
    step = jax.jit(functools.partial(tf.paged_step, cfg=cfg, page_size=ps))

    def run_one(seed, length):
        prompt = jax.random.randint(jax.random.PRNGKey(seed), (length,), 0,
                                    cfg.vocab_size)
        kv.admit(0, length + gen)
        kv.ensure(0, length)
        logits, kv.pages = step(params, prompt[None, :],
                                jnp.zeros((1,), jnp.int32),
                                jnp.asarray([length], jnp.int32),
                                kv.device_tables(), kv.pages)
        out = [logits[0]]
        tok = int(jnp.argmax(logits[0]))
        for i in range(gen):
            kv.ensure(0, length + i + 1)
            logits, kv.pages = step(params, jnp.asarray([[tok]], jnp.int32),
                                    jnp.asarray([length + i], jnp.int32),
                                    jnp.ones((1,), jnp.int32),
                                    kv.device_tables(), kv.pages)
            out.append(logits[0])
            tok = int(jnp.argmax(logits[0]))
        kv.release(0)
        return prompt, jnp.stack(out)

    run_one(3, 21)                        # dirty the pool
    prompt, got = run_one(11, 13)         # shorter seq over stale pages
    want = _dense_reference(params, cfg, prompt, gen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# Pallas paged-decode kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,kh,d,ps,pmax,np_,window,softcap", [
    (3, 8, 2, 32, 16, 8, 6, 0, 0.0),
    (2, 4, 4, 64, 8, 4, 8, 0, 30.0),
    (4, 8, 2, 32, 16, 8, 6, 20, 50.0),   # windowed + softcap
    (1, 4, 2, 16, 1, 16, 16, 0, 0.0),    # page_size = 1
])
def test_paged_kernel_matches_ref(b, h, kh, d, ps, pmax, np_, window,
                                  softcap):
    ks = jax.random.split(jax.random.PRNGKey(b * 100 + ps), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    k_pages = jax.random.normal(ks[1], (np_, kh, ps, d))
    v_pages = jax.random.normal(ks[2], (np_, kh, ps, d))
    lengths = jax.random.randint(ks[3], (b,), 1,
                                 min(pmax, np_) * ps + 1)
    bt = np.full((b, pmax), -1, np.int32)
    rng = np.random.default_rng(0)
    for i in range(b):
        need = -(-int(lengths[i]) // ps)
        bt[i, :need] = rng.choice(np_, size=need, replace=False)
    bt = jnp.asarray(bt)
    got = kops.paged_decode_attention(q, k_pages, v_pages, bt, lengths,
                                      window=window, softcap=softcap,
                                      interpret=True)
    want = paged_decode_attention_ref(q, k_pages, v_pages, bt, lengths,
                                      window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_step_use_pallas_matches(tiny):
    params, cfg = tiny
    ps, length = 8, 13
    prompt = jax.random.randint(jax.random.PRNGKey(5), (length,), 0,
                                cfg.vocab_size)

    def decode_once(use_pallas):
        kv = serve.PagedKVCache(cfg, n_slots=1, n_pages=4, page_size=ps,
                                max_len=32)
        kv.admit(0, length + 1)
        kv.ensure(0, length)
        logits, kv.pages = tf.paged_step(
            params, prompt[None, :], jnp.zeros((1,), jnp.int32),
            jnp.asarray([length], jnp.int32), kv.device_tables(), kv.pages,
            cfg, page_size=ps)
        tok = jnp.argmax(logits[0])[None, None]
        kv.ensure(0, length + 1)
        logits, _ = tf.paged_step(
            params, tok.astype(jnp.int32), jnp.asarray([length], jnp.int32),
            jnp.ones((1,), jnp.int32), kv.device_tables(), kv.pages, cfg,
            page_size=ps, use_pallas=use_pallas)
        return np.asarray(logits[0])

    np.testing.assert_allclose(decode_once(True), decode_once(False),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# MoE capacity isolation (token_mask)
# ---------------------------------------------------------------------------

def test_moe_token_mask_isolates_padding():
    cfg = get_config("granite-moe-3b-a800m", reduced=True)
    # generous capacity so every valid token is routed in both runs
    mcfg = moe_lib.MoEConfig(n_experts=cfg.moe.n_experts,
                             top_k=cfg.moe.top_k, capacity_factor=8.0,
                             dense_ff=cfg.moe.dense_ff,
                             aux_loss_coef=cfg.moe.aux_loss_coef)
    p = moe_lib.init_moe(jax.random.PRNGKey(1), cfg.d_model, cfg.d_ff, mcfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model))
    junk = jnp.concatenate(
        [x, 50.0 * jax.random.normal(jax.random.PRNGKey(3),
                                     (1, 4, cfg.d_model))], axis=1)
    mask = jnp.arange(12)[None, :] < 8
    y_clean, _ = moe_lib.moe_ffn(p, x, mcfg)
    y_mask, _ = moe_lib.moe_ffn(p, junk, mcfg, token_mask=mask)
    # masked junk consumes no capacity and cannot shift valid tokens' queue
    # positions: valid-token outputs identical, masked rows exactly zero
    np.testing.assert_allclose(np.asarray(y_mask[:, :8]),
                               np.asarray(y_clean), atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(y_mask[:, 8:]), 0.0)
    # all-True mask is bit-identical to no mask
    y_all, _ = moe_lib.moe_ffn(p, x, mcfg,
                               token_mask=jnp.ones((1, 8), bool))
    np.testing.assert_array_equal(np.asarray(y_all), np.asarray(y_clean))


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_engine_matches_sequential_baseline(tiny):
    params, cfg = tiny
    reqs = make_requests(8, cfg.vocab_size, seed=0, max_new=8)
    eng = serve.ServeEngine(params, cfg, n_slots=4, page_size=8,
                            max_len=64, prefill_chunk=16)
    outs = eng.run(reqs)
    assert [o.id for o in outs] == [r.id for r in sorted(reqs,
                                                         key=lambda r: r.id)]
    for r, o in zip(reqs, outs):
        base = serve.sequential_generate(
            params, cfg, jnp.asarray([r.prompt], jnp.int32),
            gen_len=r.max_new, cache_len=len(r.prompt) + r.max_new)
        want = tuple(int(t) for t in np.asarray(base[0, len(r.prompt):]))
        assert o.tokens == want, (r.id, o.tokens, want)
    # second wave on the SAME engine (slot + page reuse, no zeroing)
    outs2 = eng.run(reqs)
    assert [o.tokens for o in outs2] == [o.tokens for o in outs]
    st = eng.stats()
    assert st["peak_cache_bytes"] > 0
    assert st["phases"]["decode"]["count"] > 0
    assert "p95_s" in st["phases"]["decode"]


def test_engine_queueing_under_page_pressure(tiny):
    """Pool sized so only ~2 sequences fit concurrently: the rest queue
    (FCFS) and still complete with baseline-identical tokens."""
    params, cfg = tiny
    reqs = make_requests(6, cfg.vocab_size, seed=1, lens=(8, 17),
                         max_new=6)
    eng = serve.ServeEngine(params, cfg, n_slots=4, page_size=8,
                            max_len=32, n_pages=7, prefill_chunk=8)
    outs = eng.run(reqs)
    assert len(outs) == len(reqs)
    for r, o in zip(reqs, outs):
        base = serve.sequential_generate(
            params, cfg, jnp.asarray([r.prompt], jnp.int32),
            gen_len=r.max_new, cache_len=len(r.prompt) + r.max_new)
        assert o.tokens == tuple(
            int(t) for t in np.asarray(base[0, len(r.prompt):]))
    assert eng.kv.free_pages() == 7              # fully drained


def test_engine_rejects_oversized_request(tiny):
    params, cfg = tiny
    eng = serve.ServeEngine(params, cfg, n_slots=1, page_size=8, max_len=16)
    with pytest.raises(ValueError, match="exceed engine max_len"):
        eng.run([serve.Request(id=0, prompt=tuple(range(1, 15)),
                               max_new=8)])
    with pytest.raises(ValueError, match="non-empty prompt"):
        serve.Request(id=0, prompt=(), max_new=4)


# ---------------------------------------------------------------------------
# legacy generate parity pin (old break-out loop vs the engine-era baseline)
# ---------------------------------------------------------------------------

def _old_generate(params, cfg, prompts, *, gen_len, cache_len,
                  temperature=0.0, seed=0):
    """The pre-engine launch.serve.generate, verbatim semantics (including
    the ``if i == gen_len - 1: break`` tail)."""
    b, s = prompts.shape
    logits, cache = tf.prefill(params, prompts, cfg, cache_len=cache_len)
    decode = jax.jit(lambda p, t, pos, c: tf.decode_step(p, t, pos, c, cfg))
    rng = jax.random.PRNGKey(seed)
    out = [prompts]
    if temperature > 0:
        rng, sub = jax.random.split(rng)
        tok = jax.random.categorical(sub, logits / temperature)[:, None]
    else:
        tok = jnp.argmax(logits, axis=-1)[:, None]
    for i in range(gen_len):
        out.append(tok)
        if i == gen_len - 1:
            break
        logits, cache = decode(params, tok, jnp.asarray(s + i, jnp.int32),
                               cache)
        if temperature > 0:
            rng, sub = jax.random.split(rng)
            tok = jax.random.categorical(sub, logits / temperature)[:, None]
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None]
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_generate_matches_old_implementation(tiny, temperature):
    params, cfg = tiny
    prompts = jax.random.randint(jax.random.PRNGKey(9), (2, 12), 0,
                                 cfg.vocab_size)
    kw = dict(gen_len=6, cache_len=20, temperature=temperature, seed=4)
    old = _old_generate(params, cfg, prompts, **kw)
    new = launch_serve.generate(params, cfg, prompts, **kw)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_launch_serve_reduced_flag(tiny, monkeypatch):
    """--reduced used to be store_true with default=True (impossible to
    disable); pin that --no-reduced / --full now reach get_config."""
    seen = []
    real = launch_serve.get_config
    monkeypatch.setattr(
        launch_serve, "get_config",
        lambda arch, reduced=True: (seen.append(reduced),
                                    real(arch, reduced=True))[1])
    common = ["--batch", "2", "--prompt-len", "6", "--gen-len", "2",
              "--page-size", "8", "--prefill-chunk", "8"]
    toks = launch_serve.main(common)
    assert seen[-1] is True and toks.shape == (2, 8)
    launch_serve.main(common + ["--no-reduced"])
    assert seen[-1] is False
    launch_serve.main(common + ["--full"])
    assert seen[-1] is False
    launch_serve.main(common + ["--sequential"])
    assert seen[-1] is True


def test_serve_module_cli(tiny, tmp_path):
    from repro.serve.__main__ import main as serve_main
    params, cfg = tiny
    path = str(tmp_path / "m.npz")
    serve.save_serving_checkpoint(path, params, cfg)
    row = serve_main(["--checkpoint", path, "--requests", "3",
                      "--max-new", "3", "--n-slots", "2", "--page-size",
                      "8", "--max-len", "64", "--prefill-chunk", "8"])
    assert row["mode"] == "engine" and row["tokens_per_s"] > 0
    assert row["arch"] == cfg.name
    base = serve_main(["--checkpoint", path, "--requests", "2",
                       "--max-new", "2", "--baseline"])
    assert base["mode"] == "sequential" and base["tokens_per_s"] > 0
