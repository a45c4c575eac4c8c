"""Per-architecture smoke tests (assignment requirement): reduced variant of
each family — one forward/train step on CPU, asserting shapes + no NaNs —
plus decode-vs-train cache consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import transformer as tf

KEY = jax.random.PRNGKey(3)
B, S = 2, 64


def make_batch(cfg, s=S):
    tokens = jax.random.randint(KEY, (B, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    img = None
    if cfg.n_image_tokens:
        img = jax.random.normal(KEY, (B, cfg.n_image_tokens, cfg.d_model))
        batch["image_embeds"] = img
    return batch, img


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_reduced(arch):
    cfg = get_config(arch, reduced=True)
    params = tf.init_lm(KEY, cfg)
    batch, _ = make_batch(cfg)

    loss, grads = jax.value_and_grad(
        lambda p: tf.train_loss(p, batch, cfg))(params)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss))
    gleaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in gleaves)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in gleaves)

    # sgd step decreases loss on the same batch (sanity of gradients)
    params2 = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
    loss2 = tf.train_loss(params2, batch, cfg)
    assert float(loss2) < float(loss)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_reduced(arch):
    cfg = get_config(arch, reduced=True)
    params = tf.init_lm(KEY, cfg)
    batch, img = make_batch(cfg)
    logits, aux, _ = tf.forward(params, batch["tokens"], cfg, mode="train",
                                img=img)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_consistent_with_train(arch):
    cfg = get_config(arch, reduced=True)
    params = tf.init_lm(KEY, cfg)
    s = 96
    batch, img = make_batch(cfg, s)
    tokens = batch["tokens"]
    full, _, _ = tf.forward(params, tokens, cfg, mode="train", img=img)
    _, cache = tf.prefill(params, tokens[:, :s - 1], cfg, img=img,
                          cache_len=s)
    dl, _ = tf.decode_step(params, tokens[:, s - 1:s],
                           jnp.asarray(s - 1, jnp.int32), cache, cfg)
    row_err = jnp.max(jnp.abs(dl - full[:, -1]), axis=-1)  # [B]
    if cfg.moe is None:
        assert float(jnp.max(row_err)) < 1e-4, np.asarray(row_err)
    else:
        # MoE routing is bimodal per row: batched train and 1-token decode
        # see different expert loads, so a row whose last token is
        # capacity-dropped/rerouted diverges WHOLESALE while every
        # same-routing row matches the cache path to float precision.
        # Cache correctness is proven by the tight rows; rerouted rows only
        # need to stay finite and plausible.
        tight = row_err < 1e-4
        assert bool(jnp.any(tight)), np.asarray(row_err)
        assert bool(jnp.all(jnp.isfinite(dl)))
        assert float(jnp.max(row_err)) < 10.0, np.asarray(row_err)


@pytest.mark.parametrize("arch", ["gemma2-27b", "zamba2-7b"])
def test_sliding_window_changes_output(arch):
    """window must actually constrain attention for local layers."""
    import dataclasses
    cfg = get_config(arch, reduced=True)
    params = tf.init_lm(KEY, cfg)
    batch, img = make_batch(cfg, 96)
    a, _, _ = tf.forward(params, batch["tokens"], cfg, mode="train", img=img)
    cfg_wide = dataclasses.replace(cfg, window=4096)
    b_, _, _ = tf.forward(params, batch["tokens"], cfg_wide, mode="train",
                          img=img)
    assert float(jnp.max(jnp.abs(a - b_))) > 1e-6


def test_vocab_padding_masked():
    cfg = get_config("mamba2-130m", reduced=True)
    assert cfg.vocab_padded % 256 == 0
    params = tf.init_lm(KEY, cfg)
    batch, _ = make_batch(cfg)
    loss = tf.train_loss(params, batch, cfg)
    # padded rows never win: argmax of logits on valid labels only matters;
    # loss must stay below uniform over the PADDED vocab + slack if masking
    # works (it equals roughly uniform over the true vocab at init)
    assert float(loss) < np.log(cfg.vocab_padded) + 1.0


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (20, 0.0), (20, 5.0)],
                         ids=["full", "sliding", "sliding_softcap"])
def test_gqa_chunked_attention_matches_reference(window, softcap):
    """``chunked_attention`` with fewer KV heads than heads (GQA, 4 query
    heads per KV head) over several KV chunks, the last one padded, equals
    plain softmax attention with K/V repeated to the full head count,
    causal, with and without a sliding window, and with gemma2's tanh
    softcap on the scores."""
    from repro.models import attention
    b, s, h, kh, d, chunk = 2, 50, 8, 2, 16, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    out = attention.chunked_attention(q, k, v, causal=True, window=window,
                                      softcap=softcap, chunk=chunk)
    kr, vr = jnp.repeat(k, h // kh, axis=2), jnp.repeat(v, h // kh, axis=2)
    sc = jnp.einsum("bshd,bthd->bhst", q, kr) / np.sqrt(d)
    if softcap:
        sc = softcap * jnp.tanh(sc / softcap)
    pos = jnp.arange(s)
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[:, None] - pos[None, :] < window
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    ref = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, axis=-1), vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_remat_equivalent():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = tf.init_lm(KEY, cfg)
    batch, _ = make_batch(cfg)
    a = jax.grad(lambda p: tf.train_loss(p, batch, cfg, remat="none"))(params)
    b = jax.grad(lambda p: tf.train_loss(p, batch, cfg, remat="full"))(params)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_full_configs_param_counts():
    """Analytic parameter counts are in the right ballpark of the names."""
    expected = {
        "gemma2-27b": (24e9, 32e9),
        "command-r-35b": (32e9, 40e9),
        "mamba2-130m": (0.1e9, 0.2e9),
        "llama-3.2-vision-11b": (8e9, 12e9),
        "granite-moe-3b-a800m": (2.5e9, 4.5e9),
        "qwen2-72b": (65e9, 80e9),
        "tinyllama-1.1b": (0.9e9, 1.3e9),
        "musicgen-medium": (1.2e9, 2.2e9),
        "zamba2-7b": (6e9, 9e9),
        "arctic-480b": (400e9, 520e9),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).n_params()
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params_smaller():
    for arch in ("granite-moe-3b-a800m", "arctic-480b"):
        cfg = get_config(arch)
        assert cfg.n_active_params() < 0.5 * cfg.n_params()
