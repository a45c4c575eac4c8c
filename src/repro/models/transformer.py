"""Unified decoder LM covering all ten assigned architectures.

A model is a repeating *period* of block kinds (see configs.base.ModelConfig):

    dense   self-attention (full causal) + SwiGLU MLP
    local   self-attention with sliding window
    global  full self-attention (alias of dense; used in alternating patterns)
    moe     self-attention + mixture-of-experts FFN (optional dense residual)
    mamba   Mamba-2 SSD mixer (no MLP)
    cross   gated cross-attention to image embeddings + gated MLP (VLM)

The main stack is ``lax.scan`` over periods (stacked params, compact HLO);
``tail_layers`` and the zamba2 shared-attention block are applied outside the
scan.  Four entry points: ``train_loss`` (tokens+labels -> scalar),
``prefill`` (tokens -> last logits + KV caches), ``decode_step`` (one token +
caches -> logits + caches), and ``paged_step`` (a chunk of tokens per serving
slot against the paged KV pool — the continuous-batching serving path,
DESIGN.md §13: every slot carries its own absolute position, K/V are
scattered into fixed-size pages addressed by a per-slot block table, and
attention gathers the slot's pages back; one traced shape handles chunked
prefill (chunk=C) and batched decode (chunk=1)).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Any, Optional

import jax
import jax.numpy as jnp

from . import attention, layers, moe as moe_lib, ssm as ssm_lib

if TYPE_CHECKING:  # avoid configs <-> models import cycle
    from repro.configs.base import ModelConfig
else:
    ModelConfig = Any

PyTree = Any

ATTN_KINDS = ("dense", "local", "global", "moe")


@dataclasses.dataclass(frozen=True)
class RunCtx:
    cfg: ModelConfig
    mode: str                       # train | prefill | decode
    pos: Any = None                 # decode: scalar current position
    img: Any = None                 # vlm: [B, T_img, d] stub embeddings
    chunk: int = 1024               # attention KV-chunk size
    ssd_chunk: int = 128
    cache_len: int = 0              # prefill: total KV capacity (>= seq len)
    use_pallas: bool = False
    remat: str = "none"             # none | full
    pages: Any = None               # paged mode: PageInfo (block tables etc.)


@dataclasses.dataclass(frozen=True)
class PageInfo:
    """Per-call paged-KV addressing, computed ONCE in :func:`paged_step` and
    shared by every attention layer (pages are per-layer, the block table is
    per-slot).  Token ``i`` of slot ``b`` sits at absolute position
    ``q_pos[b, i]``; its page-pool row is ``scatter_idx[b*C + i]`` (an
    out-of-bounds sentinel drops writes for inactive slots / prompt
    overhang).  ``gather_idx[b, t]`` maps the slot's logical position ``t``
    back to a pool row — positions beyond the allocated pages clip to row 0
    and are killed by the causal mask (``t`` <= current position implies the
    row was written by THIS sequence, so slot/page reuse needs no cache
    zeroing)."""

    q_pos: Any          # [B, C] int32 absolute positions of the chunk
    scatter_idx: Any    # [B*C] int32 flat pool rows (OOB sentinel = drop)
    gather_idx: Any     # [B, T] int32 pool row per logical position
    last_idx: Any       # [B] int32 chunk index of the last valid token
    block_tables: Any   # [B, P] int32 page ids, -1 = unallocated
    lengths: Any        # [B] int32 slot length AFTER this chunk lands
    token_mask: Any = None  # [B, C] bool — False on padded/junk chunk rows
    use_pallas: bool = False   # decode (C==1): gather-free Pallas kernel


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, kind: str, cfg: ModelConfig, dtype) -> PyTree:
    d, f = cfg.d_model, cfg.d_ff
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    if kind == "mamba":
        return {"ln": jnp.zeros((d,), dtype),
                "mixer": ssm_lib.init_mamba(ks[0], d, cfg.ssm, dtype)}
    if kind == "cross":
        return {
            "ln1": jnp.zeros((d,), dtype),
            "xattn": attention.init_attention(
                ks[0], d, cfg.n_heads, cfg.n_kv_heads, hd, dtype=dtype),
            "gate_attn": jnp.zeros((), jnp.float32),
            "ln2": jnp.zeros((d,), dtype),
            "mlp": layers.init_mlp(ks[1], d, f, dtype),
            "gate_mlp": jnp.zeros((), jnp.float32),
        }
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "attn": attention.init_attention(
            ks[0], d, cfg.n_heads, cfg.n_kv_heads, hd,
            qkv_bias=cfg.qkv_bias, dtype=dtype),
        "ln2": jnp.zeros((d,), dtype),
    }
    if kind == "moe":
        p["moe"] = moe_lib.init_moe(ks[1], d, f, cfg.moe, dtype)
    else:
        p["mlp"] = layers.init_mlp(ks[1], d, f, dtype)
    return p


def init_lm(key, cfg: ModelConfig, dtype=jnp.float32) -> PyTree:
    keys = jax.random.split(key, 8)
    vp = cfg.vocab_padded
    params: dict[str, Any] = {
        "embed": layers.embed_init(keys[0], vp, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(keys[1], cfg.d_model, vp, dtype)
    # main scanned stack: per period position, params stacked over n_periods
    blocks = []
    for j, kind in enumerate(cfg.period):
        bkeys = jax.random.split(jax.random.fold_in(keys[2], j), cfg.n_periods)
        blocks.append(layers.stack_layers(
            bkeys, lambda k: _init_block(k, kind, cfg, dtype)))
    params["blocks"] = tuple(blocks)
    params["tail"] = tuple(
        _init_block(jax.random.fold_in(keys[3], i), cfg.period[0], cfg, dtype)
        for i in range(cfg.tail_layers))
    if cfg.shared_attn_every:
        params["shared_attn"] = _init_block(keys[4], "dense", cfg, dtype)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _attn_cache_len(kind: str, cfg: ModelConfig, cache_len: int) -> int:
    if kind == "local" and cfg.window:
        return min(cfg.window, cache_len)
    return cache_len


def _empty_block_cache(kind: str, cfg: ModelConfig, batch: int,
                       cache_len: int, dtype):
    hd = cfg.resolved_head_dim
    if kind == "mamba":
        return ssm_lib.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
    if kind == "cross":
        t = cfg.n_image_tokens
        return {"k": jnp.zeros((batch, t, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, t, cfg.n_kv_heads, hd), dtype)}
    length = _attn_cache_len(kind, cfg, cache_len)
    return {
        "k": jnp.zeros((batch, length, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, length, cfg.n_kv_heads, hd), dtype),
        "slot_pos": jnp.full((length,), -1, jnp.int32),
    }


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               dtype=jnp.float32) -> PyTree:
    def stacked(kind):
        one = _empty_block_cache(kind, cfg, batch, cache_len, dtype)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), one)

    cache: dict[str, Any] = {
        "blocks": tuple(stacked(kind) for kind in cfg.period),
        "tail": tuple(
            _empty_block_cache(cfg.period[0], cfg, batch, cache_len, dtype)
            for _ in range(cfg.tail_layers)),
    }
    if cfg.shared_attn_every:
        # one KV cache per use-site (the shared block runs once per period)
        cache["shared_attn"] = stacked("local" if cfg.window else "dense")
    return cache


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _paged_self_attn(p, x, window: int, ctx: RunCtx, cache):
    """Paged-KV attention for one layer: scatter the chunk's K/V into the
    layer's page pool, then attend over the slot's gathered pages (or the
    gather-free Pallas kernel for single-token decode).  ``cache`` is
    ``{"k": [NP, KH, ps, D], "v": ...}`` — the pool, NOT a per-slot
    buffer.  A flat pool row ``r`` is page ``r // ps``, offset ``r % ps``."""
    cfg, pg = ctx.cfg, ctx.pages
    hd = cfg.resolved_head_dim
    b, c, _ = x.shape
    q, k, v = attention.qkv(p, x, cfg.n_heads, cfg.n_kv_heads, hd)
    q = layers.apply_rope(q, pg.q_pos, cfg.rope_theta)
    k = layers.apply_rope(k, pg.q_pos, cfg.rope_theta)
    kh, ps = cache["k"].shape[1], cache["k"].shape[2]
    sp, so = pg.scatter_idx // ps, pg.scatter_idx % ps
    new_cache = {
        "k": cache["k"].at[sp, :, so].set(k.reshape(b * c, kh, hd),
                                          mode="drop"),
        "v": cache["v"].at[sp, :, so].set(v.reshape(b * c, kh, hd),
                                          mode="drop")}
    if pg.use_pallas and c == 1:
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(
            q, new_cache["k"], new_cache["v"], pg.block_tables, pg.lengths,
            window=window, softcap=cfg.attn_softcap)
    else:
        gp, go = pg.gather_idx // ps, pg.gather_idx % ps
        ks = new_cache["k"][gp, :, go]             # [B, T, KH, D]
        vs = new_cache["v"][gp, :, go]
        out = attention.paged_attention(q, ks, vs, pg.q_pos, window=window,
                                        softcap=cfg.attn_softcap)
    out = out.reshape(b, c, cfg.n_heads * hd)
    return jnp.einsum("...f,fd->...d", out, p["wo"]), new_cache


def _self_attn(p, x, kind: str, ctx: RunCtx, cache):
    cfg = ctx.cfg
    hd = cfg.resolved_head_dim
    window = cfg.window if kind == "local" else 0
    if ctx.mode == "paged":
        return _paged_self_attn(p, x, window, ctx, cache)
    if ctx.mode == "decode":
        b = x.shape[0]
        q, k, v = attention.qkv(p, x, cfg.n_heads, cfg.n_kv_heads, hd)
        q = layers.apply_rope(q, ctx.pos + jnp.zeros((b, 1), jnp.int32),
                              cfg.rope_theta)
        k = layers.apply_rope(k, ctx.pos + jnp.zeros((b, 1), jnp.int32),
                              cfg.rope_theta)
        length = cache["k"].shape[1]
        slot = ctx.pos % length
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, 1)
        slot_pos = jax.lax.dynamic_update_slice_in_dim(
            cache["slot_pos"], ctx.pos[None].astype(jnp.int32), slot, 0)
        out = attention.decode_attention(
            q, k_cache, v_cache, ctx.pos, window=window,
            softcap=cfg.attn_softcap, k_pos=slot_pos)
        new_cache = {"k": k_cache, "v": v_cache, "slot_pos": slot_pos}
    else:
        b, s, _ = x.shape
        q, k, v = attention.qkv(p, x, cfg.n_heads, cfg.n_kv_heads, hd)
        pos = jnp.arange(s)[None, :]
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)
        if ctx.use_pallas:
            from repro.kernels import ops as kops
            out = kops.flash_attention(
                q, k, v, causal=True, window=window, softcap=cfg.attn_softcap)
        else:
            out = attention.chunked_attention(
                q, k, v, causal=True, window=window,
                softcap=cfg.attn_softcap, chunk=ctx.chunk)
        new_cache = None
        if ctx.mode == "prefill":
            cap = max(ctx.cache_len, s)
            length = _attn_cache_len(kind, cfg, cap)
            if length >= s:  # pad; position p sits at slot p % length == p
                pad = length - s
                kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                slot_pos = jnp.concatenate(
                    [jnp.arange(s, dtype=jnp.int32),
                     jnp.full((pad,), -1, jnp.int32)])
            else:  # ring buffer: keep last `length`, slot = pos % length
                positions = jnp.arange(s - length, s, dtype=jnp.int32)
                shift = int((s - length) % length)
                kc = jnp.roll(k[:, s - length:], shift, axis=1)
                vc = jnp.roll(v[:, s - length:], shift, axis=1)
                slot_pos = jnp.roll(positions, shift)
            new_cache = {"k": kc, "v": vc, "slot_pos": slot_pos}
    out = out.reshape(out.shape[0], out.shape[1], cfg.n_heads * hd)
    return jnp.einsum("...f,fd->...d", out, p["wo"]), new_cache


def apply_block(kind: str, p, x, ctx: RunCtx, cache):
    cfg = ctx.cfg
    aux = jnp.zeros((), jnp.float32)
    if ctx.mode == "paged" and kind not in ATTN_KINDS:
        raise NotImplementedError(
            f"paged serving supports attention-only stacks; block kind "
            f"{kind!r} (mamba/cross state caches are per-slot, not paged)")
    if kind == "mamba":
        h = layers.rms_norm(x, p["ln"], cfg.norm_eps)
        if ctx.mode == "decode":
            out, new_cache = ssm_lib.mamba_decode(p["mixer"], h, cache, cfg.ssm)
        else:
            out = ssm_lib.mamba_mixer(p["mixer"], h, cfg.ssm,
                                      chunk=ctx.ssd_chunk,
                                      use_pallas=ctx.use_pallas)
            new_cache = cache  # prefill state handled via chunked final state
            if ctx.mode == "prefill":
                # recompute final state cheaply through the chunked path
                new_cache = _mamba_prefill_state(p["mixer"], h, cfg.ssm,
                                                 ctx.ssd_chunk)
        return x + out, aux, new_cache

    if kind == "cross":
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        hd = cfg.resolved_head_dim
        if ctx.mode == "decode":
            b = x.shape[0]
            q = attention._proj(h, p["xattn"]["wq"]).reshape(
                b, 1, cfg.n_heads, hd)
            out = attention.decode_attention(
                q, cache["k"], cache["v"],
                jnp.asarray(cache["k"].shape[1] - 1, jnp.int32))
            out = out.reshape(b, 1, cfg.n_heads * hd)
            out = jnp.einsum("...f,fd->...d", out, p["xattn"]["wo"])
            new_cache = cache
        else:
            out = attention.cross_attention(
                p["xattn"], h, ctx.img, cfg.n_heads, cfg.n_kv_heads, hd)
            new_cache = None
            if ctx.mode == "prefill":
                b, t, _ = ctx.img.shape
                k = attention._proj(ctx.img, p["xattn"]["wk"]).reshape(
                    b, t, cfg.n_kv_heads, hd)
                v = attention._proj(ctx.img, p["xattn"]["wv"]).reshape(
                    b, t, cfg.n_kv_heads, hd)
                new_cache = {"k": k, "v": v}
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * out
        h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
        m = layers.swiglu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
        x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * m
        return x, aux, new_cache

    # attention + (mlp | moe)
    h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
    out, new_cache = _self_attn(p["attn"], h, kind, ctx, cache)
    x = x + out
    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "moe":
        # paged batches carry junk beyond each slot's n_valid; keep it out
        # of the capacity queues (see moe_ffn docstring)
        tm = ctx.pages.token_mask if ctx.mode == "paged" else None
        y, aux = moe_lib.moe_ffn(p["moe"], h, cfg.moe, token_mask=tm)
    else:
        y = layers.swiglu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
    return x + y, aux, new_cache


def _mamba_prefill_state(mixer, h, scfg, chunk):
    """Final (conv, ssm) state after consuming h [B,S,d] — for prefill."""
    bsz, s, d_model = h.shape
    di = scfg.d_inner(d_model)
    nh = scfg.n_heads(d_model)
    n = scfg.d_state
    proj = jnp.einsum("bsd,df->bsf", h, mixer["in_proj"])
    _, xbc_raw, dt = ssm_lib._split_proj(proj, di, n, nh)
    xbc = ssm_lib._causal_conv(xbc_raw, mixer["conv_w"])
    xi = xbc[..., :di].reshape(bsz, s, nh, scfg.head_dim)
    b = xbc[..., di:di + n]
    c = xbc[..., di + n:]
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + mixer["dt_bias"])
    a = -jnp.exp(mixer["a_log"])
    _, hfin = ssm_lib.ssd_chunked(xi, dtv, a, b, c, mixer["d_skip"],
                                  chunk=min(chunk, s))
    kconv = mixer["conv_w"].shape[0]
    conv_state = xbc_raw[:, s - (kconv - 1):, :]
    return {"conv": conv_state, "ssm": hfin}


# ---------------------------------------------------------------------------
# full model passes
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg):
    return jnp.take(params["embed"], tokens, axis=0)


def _head(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _logits(params, x, cfg: ModelConfig):
    h = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("...d,dv->...v", h, _head(params, cfg))
    return layers.softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def _shared_attn_block(p, x, ctx: RunCtx, cache):
    """zamba2: ONE param set, applied at every period boundary inside the
    scan (period = (mamba,)*shared_attn_every), with a per-use-site KV
    cache (stacked over periods like the backbone caches)."""
    kind = "local" if ctx.cfg.window else "dense"
    h = layers.rms_norm(x, p["ln1"], ctx.cfg.norm_eps)
    out, new_cache = _self_attn(p["attn"], h, kind, ctx, cache)
    x = x + out
    h = layers.rms_norm(x, p["ln2"], ctx.cfg.norm_eps)
    x = x + layers.swiglu(h, p["mlp"]["gate"], p["mlp"]["up"],
                          p["mlp"]["down"])
    return x, new_cache


def forward(params, tokens, cfg: ModelConfig, *, mode: str,
            img=None, cache=None, pos=None, chunk: int = 1024,
            ssd_chunk: int = 128, cache_len: int = 0,
            use_pallas: bool = False, remat: str = "none", pages=None,
            head: bool = True):
    """Shared driver. Returns (logits, aux_loss, new_cache).

    train:   tokens [B,S]   -> logits [B,S,Vp], aux, None
             (``head=False``: the last layer's output [B,S,d] in place of
             the logits, for a loss that applies the head itself)
    prefill: tokens [B,S]   -> logits [B,Vp] (last pos), aux, cache
    decode:  tokens [B,1]   -> logits [B,Vp], aux, cache
    paged:   tokens [B,C]   -> logits [B,Vp] (per-slot last valid), aux, pages
    """
    ctx = RunCtx(cfg=cfg, mode=mode, pos=pos, img=img, chunk=chunk,
                 ssd_chunk=ssd_chunk, cache_len=cache_len,
                 use_pallas=use_pallas, remat=remat, pages=pages)
    x = _embed(params, tokens, cfg)
    aux_total = jnp.zeros((), jnp.float32)
    with_cache = mode in ("prefill", "decode", "paged")

    shared_p = params.get("shared_attn")

    def period_body(x, block_params, block_caches, shared_cache):
        aux_p = jnp.zeros((), jnp.float32)
        new_caches = []
        for j, kind in enumerate(cfg.period):
            c = block_caches[j] if block_caches is not None else None
            x, aux, nc = apply_block(kind, block_params[j], x, ctx, c)
            aux_p = aux_p + aux
            new_caches.append(nc)
        new_shared = None
        if shared_p is not None:
            x, new_shared = _shared_attn_block(shared_p, x, ctx, shared_cache)
        return x, aux_p, tuple(new_caches), new_shared

    if remat == "full":
        period_body = jax.checkpoint(period_body)

    def scan_fn(carry, xs):
        x, aux_acc = carry
        if mode in ("decode", "paged"):
            bp, bc, sc = xs
        else:
            (bp,), bc, sc = xs, None, None
        x, aux_p, ncs, nsc = period_body(x, bp, bc, sc)
        out = (ncs, nsc) if with_cache else None
        return (x, aux_acc + aux_p), out

    if mode in ("decode", "paged"):
        shared_c = cache.get("shared_attn") if shared_p is not None else None
        xs = (params["blocks"], cache["blocks"], shared_c)
    else:
        xs = (params["blocks"],)
    (x, aux_total), scan_out = jax.lax.scan(scan_fn, (x, aux_total), xs)

    new_cache: dict[str, Any] = {}
    if with_cache:
        new_cache["blocks"] = scan_out[0]
        if shared_p is not None:
            new_cache["shared_attn"] = scan_out[1]

    tail_caches = []
    for i, tp in enumerate(params["tail"]):
        c = cache["tail"][i] if mode in ("decode", "paged") else None
        x, aux, nc = apply_block(cfg.period[0], tp, x, ctx, c)
        aux_total = aux_total + aux
        tail_caches.append(nc)
    if with_cache:
        new_cache["tail"] = tuple(tail_caches)

    if mode == "train":
        return (_logits(params, x, cfg) if head else x), aux_total, None
    if mode == "prefill":
        return _logits(params, x[:, -1], cfg), aux_total, new_cache
    if mode == "paged":
        li = jnp.broadcast_to(pages.last_idx[:, None, None],
                              (x.shape[0], 1, x.shape[2]))
        x_last = jnp.take_along_axis(x, li, axis=1)[:, 0]
        return _logits(params, x_last, cfg), aux_total, new_cache
    return _logits(params, x[:, 0], cfg), aux_total, new_cache


# tokens per block of the training loss's head: one block's fp32 logits
# (1024 x 50432 for mamba2-130m: 0.21 GB) stand in for the whole
# [tokens, vocab] logits and their gradient
HEAD_BLOCK = 1024


def train_loss(params, batch, cfg: ModelConfig, **kw):
    """batch: {tokens [B,S], labels [B,S], (image_embeds)} -> scalar loss.

    The output head and the cross-entropy run ``HEAD_BLOCK`` tokens at a
    time (``layers.blocked_cross_entropy``, under the ``tm/lm_head``
    scope); ``kw`` goes to :func:`forward`
    (``remat='full'`` rematerialises each period of the layer scan in the
    backward pass)."""
    x, aux, _ = forward(params, batch["tokens"], cfg, mode="train",
                        img=batch.get("image_embeds"), head=False, **kw)
    with jax.named_scope("tm/lm_head"):
        h = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        ce = layers.blocked_cross_entropy(
            h, _head(params, cfg), batch["labels"], cfg.vocab_size,
            cap=cfg.logit_softcap, block=HEAD_BLOCK)
    return ce + aux


def prefill(params, tokens, cfg: ModelConfig, *, img=None, **kw):
    logits, _, cache = forward(params, tokens, cfg, mode="prefill", img=img, **kw)
    return logits, cache


def decode_step(params, token, pos, cache, cfg: ModelConfig, **kw):
    """token [B,1] int32, pos scalar int32, cache from init_cache/prefill."""
    logits, _, new_cache = forward(params, token, cfg, mode="decode",
                                   cache=cache, pos=pos, **kw)
    return logits, new_cache


# ---------------------------------------------------------------------------
# paged serving (continuous batching — DESIGN.md §13)
# ---------------------------------------------------------------------------

def supports_paged(cfg: ModelConfig) -> bool:
    """Paged serving covers attention-only stacks (dense/local/global/moe).
    Mamba conv/SSM states and VLM cross caches are O(1) per slot and would
    need per-slot (not paged) storage; the zamba2 shared block is mamba-
    interleaved anyway."""
    return (all(k in ATTN_KINDS for k in cfg.period)
            and not cfg.shared_attn_every and not cfg.n_image_tokens)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.float32) -> PyTree:
    """One K/V page pool per attention layer, mirroring :func:`init_cache`'s
    structure (period-stacked ``blocks`` + ``tail``) so the same scan
    consumes it.  There is no batch axis: slots address the shared pool
    through their block tables."""
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"{cfg.name}: paged serving supports attention-only stacks "
            f"(period={cfg.period}, shared_attn_every="
            f"{cfg.shared_attn_every}, n_image_tokens={cfg.n_image_tokens})")
    hd = cfg.resolved_head_dim

    def one():
        return {"k": jnp.zeros((n_pages, cfg.n_kv_heads, page_size, hd),
                               dtype),
                "v": jnp.zeros((n_pages, cfg.n_kv_heads, page_size, hd),
                               dtype)}

    def stacked():
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_periods,) + x.shape), one())

    return {"blocks": tuple(stacked() for _ in cfg.period),
            "tail": tuple(one() for _ in range(cfg.tail_layers))}


def paged_step(params, tokens, pos, n_valid, block_tables, pages,
               cfg: ModelConfig, *, page_size: int,
               use_pallas: bool = False):
    """One serving step: each slot consumes a chunk of C tokens at its own
    absolute position.  C == 1 is batched decode; C == prefill_chunk is one
    chunked-prefill slice — the SAME trace serves both, so the engine
    compiles exactly two instances and never recompiles on admission or
    eviction (slot liveness is data: ``n_valid == 0`` masks a row).

    tokens        [B, C] int32 (junk beyond ``n_valid`` is masked)
    pos           [B]    int32 start position of the chunk per slot
    n_valid       [B]    int32 valid tokens in the chunk (0 = inactive slot)
    block_tables  [B, P] int32 page ids, -1 = unallocated
    pages         pytree from :func:`init_paged_cache`

    Returns ``(logits [B, Vp] at each slot's last valid token, new_pages)``.
    """
    b, c = tokens.shape
    p_max = block_tables.shape[1]
    t_total = p_max * page_size
    n_pages = jax.tree.leaves(pages)[0].shape[-4]

    q_pos = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    valid = jnp.arange(c)[None, :] < n_valid[:, None]
    page_slot = jnp.clip(q_pos // page_size, 0, p_max - 1)
    page_of = jnp.take_along_axis(block_tables, page_slot, axis=1)
    flat = page_of * page_size + q_pos % page_size
    # invalid rows scatter to one-past-the-pool: mode="drop" discards them
    scatter_idx = jnp.where(valid & (page_of >= 0), flat,
                            n_pages * page_size).reshape(b * c)
    t_idx = jnp.arange(t_total, dtype=jnp.int32)
    gather_pages = block_tables[:, t_idx // page_size]
    # unallocated positions clip to pool row 0; they sit at logical positions
    # >= the slot's length, so the causal mask in paged_attention kills them
    gather_idx = jnp.clip(gather_pages * page_size + t_idx % page_size,
                          0, n_pages * page_size - 1)
    pi = PageInfo(q_pos=q_pos, scatter_idx=scatter_idx,
                  gather_idx=gather_idx,
                  last_idx=jnp.clip(n_valid - 1, 0),
                  block_tables=block_tables, lengths=pos + n_valid,
                  token_mask=valid, use_pallas=use_pallas)
    logits, _, new_pages = forward(params, tokens, cfg, mode="paged",
                                   cache=pages, pages=pi)
    return logits, new_pages
