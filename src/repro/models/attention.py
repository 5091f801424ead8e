"""Grouped-query attention with RoPE, KV cache, optional sliding window and
optional QK-norm (chameleon).  Pure functions over a params dict.

Layouts: activations [B, S, D]; q/k/v [B, S, H, hd]; KV cache [B, S_max, KV, hd].
TP: q heads sharded over 'model' when divisible (logical axis "act_heads");
the out-projection is row-parallel — XLA inserts the psum.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers
from repro.models.params import ParamSpec

NEG_INF = -2.3819763e38


class KVCache(NamedTuple):
    k: jax.Array          # [B, S_max, KV, hd]
    v: jax.Array          # [B, S_max, KV, hd]
    length: jax.Array     # int32[] — tokens currently in the cache


def attn_specs(cfg) -> dict:
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    s = {
        "wq": ParamSpec((cfg.d_model, cfg.n_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv", "head_dim")),
        "wv": ParamSpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv", "head_dim")),
        "wo": ParamSpec((cfg.n_heads, hd, cfg.d_model), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = layers.rmsnorm_spec(hd)
        s["k_norm"] = layers.rmsnorm_spec(hd)
    return s


def _qkv(p, cfg, x, positions):
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = constrain(q, "batch", None, "act_heads", None)
    if cfg.qk_norm:
        q = layers.rmsnorm(q, p["q_norm"])
        k = layers.rmsnorm(k, p["k_norm"])
    if cfg.rope_theta and cfg.position_embedding == "rope":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v, hd


def _sdpa(q, k, v, mask, hd, scale=None):
    """q: [B,S,H,hd]; k/v: [B,T,KV,hd]; GQA via head grouping.  Scores are
    multiplied by ``scale`` when given, else divided by sqrt(hd)."""
    b, s, h, _ = q.shape
    kv = k.shape[2]
    group = h // kv
    qg = q.reshape(b, s, kv, group, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    if scale is None:
        logits = logits / jnp.sqrt(hd).astype(jnp.float32)
    else:
        logits = logits * jnp.float32(scale)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, hd)


def causal_mask(s: int, window: Optional[int] = None) -> jax.Array:
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    m = j <= i
    if window is not None:
        m &= j > i - window
    return m[None]  # [1, S, S]


def self_attention(
    p: dict,
    cfg,
    x: jax.Array,                  # [B, S, D]
    *,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    window: Optional[int] = None,
    key_lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """``key_lengths`` (int32[B]): row b attends only to its first
    ``key_lengths[b]`` positions (right-padded rows)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v, hd = _qkv(p, cfg, x, positions)
    if causal:
        mask = causal_mask(s, window)
    else:
        mask = jnp.ones((1, s, s), bool)
    if key_lengths is not None:
        mask = mask & (jnp.arange(s)[None, None, :] < key_lengths[:, None, None])
    out = _sdpa(q, k, v, mask, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return constrain(out, "batch", None, "act_embed")


def prefill_attention(p, cfg, x, cache: KVCache, *, window=None):
    """Full-sequence prefill that also fills the KV cache.

    With a sliding window and a ring cache smaller than the prompt, only the
    last ``window`` tokens are stored, rotated so token t sits at slot
    t % window — the exact layout decode_attention continues from."""
    b, s, _ = x.shape
    s_max = cache.k.shape[1]
    positions = jnp.arange(s)[None, :]
    q, k, v, hd = _qkv(p, cfg, x, positions)
    k_st, v_st = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
    if window and s > s_max:
        assert s_max == window, (s_max, window)
        k_tail, v_tail = k_st[:, -window:], v_st[:, -window:]
        shift = (s - window) % window
        k_st = jnp.roll(k_tail, shift, axis=1)
        v_st = jnp.roll(v_tail, shift, axis=1)
    k_cache = jax.lax.dynamic_update_slice(cache.k, k_st, (0, 0, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache.v, v_st, (0, 0, 0, 0))
    mask = causal_mask(s, window)
    out = _sdpa(q, k, v, mask, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    new_cache = KVCache(k=k_cache, v=v_cache, length=jnp.asarray(s, jnp.int32))
    return constrain(out, "batch", None, "act_embed"), new_cache


def decode_attention(p, cfg, x, cache: KVCache, *, window=None):
    """One-token decode step against the KV cache.

    x: [B, 1, D].  The cache holds ``cache.length`` valid tokens; the new
    token is written at position ``length`` (ring-buffered when a sliding
    window is active — the window case sizes the cache to the window).
    """
    b, one, _ = x.shape
    s_max = cache.k.shape[1]
    pos = cache.length
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v, hd = _qkv(p, cfg, x, positions)
    slot = (pos % s_max) if window else pos
    k_cache = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
    # valid slots: the first length+1 (linear cache), or the whole ring once
    # it has wrapped (window case — cache is sized to the window)
    j = jnp.arange(s_max)                                    # [S]
    valid = j < jnp.minimum(pos + 1, s_max) if window else j < pos + 1
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, s_max))
    out = _sdpa(q, k_cache, v_cache, mask, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    new_cache = KVCache(k=k_cache, v=v_cache, length=pos + 1)
    return constrain(out, "batch", None, "act_embed"), new_cache


def prefill_attention_rows(p, cfg, x, lengths):
    """Causal self-attention (within ``cfg.window`` when set) over
    right-padded prompts, returning the K/V rows a cache keeps: rows at or
    past ``lengths[b]`` are zero, as an unpadded prompt leaves them.
    x: [B, S, D]; lengths: int32[B].  Returns (out [B, S, D], k, v
    [B, S, KV, hd] in x's dtype)."""
    b, s, _ = x.shape
    q, k, v, hd = _qkv(p, cfg, x, jnp.arange(s)[None, :])
    out = _sdpa(q, k, v, causal_mask(s, cfg.window), hd, cfg.attention_multiplier)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    real = (jnp.arange(s)[None, :] < lengths[:, None])[..., None, None]
    k = jnp.where(real, k, 0).astype(x.dtype)
    v = jnp.where(real, v, 0).astype(x.dtype)
    return constrain(out, "batch", None, "act_embed"), k, v


def decode_attention_slots(p, cfg, x, k_cache, v_cache, lengths):
    """One-token decode for B slots of different lengths.

    x: [B, 1, D]; k/v_cache: [B, S_max, KV, hd] hold each slot's first
    ``lengths[b]`` tokens.  The new token attends to those (the last
    ``cfg.window - 1`` of them when a window is set) and to itself.
    Returns (out [B, 1, D], k, v [B, KV, hd]); the caller writes k and v at
    row ``lengths[b]`` of each slot, so the cache is only read here."""
    b = x.shape[0]
    s_max = k_cache.shape[1]
    q, k, v, hd = _qkv(p, cfg, x, lengths[:, None])
    kv = k_cache.shape[2]
    qg = q.reshape(b, 1, kv, q.shape[2] // kv, hd)
    cached = jnp.einsum("bskgd,btkd->bkgst", qg, k_cache).astype(jnp.float32)
    own = jnp.einsum("bskgd,bskd->bkgs", qg, k.astype(k_cache.dtype)).astype(jnp.float32)
    scores = jnp.concatenate([cached, own[..., None]], axis=-1)      # [B,KV,G,1,S+1]
    scores = (scores / jnp.sqrt(hd).astype(jnp.float32) if cfg.attention_multiplier is None
              else scores * jnp.float32(cfg.attention_multiplier))
    j = jnp.arange(s_max)[None, :]
    seen = j < lengths[:, None]
    if cfg.window:
        seen &= j > lengths[:, None] - cfg.window
    valid = jnp.concatenate([seen, jnp.ones((b, 1), bool)], axis=1)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = (jnp.einsum("bkgst,btkd->bskgd", probs[..., :s_max], v_cache)
           + jnp.einsum("bkgs,bskd->bskgd", probs[..., s_max], v.astype(v_cache.dtype)))
    out = jnp.einsum("bshk,hkd->bsd", out.reshape(b, 1, -1, hd), p["wo"])
    return constrain(out, "batch", None, "act_embed"), k[:, 0], v[:, 0]


def cross_attention_specs(cfg) -> dict:
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    return {
        "wq": ParamSpec((cfg.d_model, cfg.n_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv", "head_dim")),
        "wv": ParamSpec((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv", "head_dim")),
        "wo": ParamSpec((cfg.n_heads, hd, cfg.d_model), ("heads", "head_dim", "embed")),
    }


def cross_attention(p, cfg, x, memory, memory_kv=None, memory_lengths=None):
    """Decoder cross-attention.  memory: [B, T, D] encoder output; if
    memory_kv (precomputed K/V of the memory) is given, reuse it.  With
    ``memory_lengths`` (int32[B]) row b attends only to its first
    ``memory_lengths[b]`` memory positions."""
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = constrain(q, "batch", None, "act_heads", None)
    if memory_kv is None:
        k = jnp.einsum("btd,dhk->bthk", memory, p["wk"])
        v = jnp.einsum("btd,dhk->bthk", memory, p["wv"])
    else:
        k, v = memory_kv
    b, s = q.shape[0], q.shape[1]
    mask = jnp.ones((1, s, k.shape[1]), bool)
    if memory_lengths is not None:
        mask = mask & (jnp.arange(k.shape[1])[None, None, :] < memory_lengths[:, None, None])
    out = _sdpa(q, k, v, mask, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return constrain(out, "batch", None, "act_embed")


def init_cache(cfg, batch: int, s_max: int, dtype=jnp.bfloat16) -> KVCache:
    hd = cfg.head_dim or cfg.d_model // cfg.n_heads
    shape = (batch, s_max, cfg.n_kv_heads, hd)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        length=jnp.zeros((), jnp.int32),
    )


def cache_axes() -> KVCache:
    """Logical axes for the cache pytree (for dry-run shardings)."""
    ax = ("cache_batch", "cache_seq", "cache_kv", "head_dim")
    return KVCache(k=ax, v=ax, length=())
