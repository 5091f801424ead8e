"""Mamba2 (SSD) blocks — chunked parallel training form + O(1) decode step.

Scalar-per-head decay SSD (Dao & Gu 2024), ngroups=1.  Shapes:
  x  [B, S, H, P]   (P = headdim, H = d_inner/P)
  dt [B, S, H]      (softplus(dt_raw + bias))
  A  [H]            (negative: -exp(A_log))
  B,C [B, S, N]     (state dim N, shared across heads; ngroups=1)

The chunked algorithm splits S into chunks of L: quadratic attention-like
intra-chunk term + an inter-chunk state recurrence (lax.scan over chunks) —
sub-quadratic overall, which is what qualifies the hybrid archs for the
long_500k shape.  Heads are sharded over 'model' (logical "ssm_heads").
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import layers
from repro.models.params import ParamSpec

CONV_K = 4


class MambaCache(NamedTuple):
    ssm: jax.Array     # [B, H, N, P] state
    conv: jax.Array    # [B, CONV_K-1, conv_dim] rolling conv input buffer
    length: jax.Array  # int32[]


def dims(cfg):
    """(d_inner, heads, conv channels) from the config's Mamba2 sizes."""
    assert cfg.ssm_groups == 1, "only one B/C group is implemented"
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = cfg.ssm_heads or d_inner // cfg.ssm_head_dim
    assert n_heads * cfg.ssm_head_dim == d_inner, (n_heads, cfg.ssm_head_dim, d_inner)
    conv_dim = d_inner + 2 * cfg.ssm_state
    return d_inner, n_heads, conv_dim


def mamba_specs(cfg) -> dict:
    d, n = cfg.d_model, cfg.ssm_state
    d_inner, h, conv_dim = dims(cfg)
    common = {
        "A_log": ParamSpec((h,), (None,), init="zeros", dtype=jnp.float32),
        "D": ParamSpec((h,), (None,), init="ones", dtype=jnp.float32),
        "dt_bias": ParamSpec((h,), (None,), init="zeros", dtype=jnp.float32),
        "norm": layers.rmsnorm_spec(d_inner),
        "out_proj": ParamSpec((d_inner, d), ("ssm_inner", "embed")),
    }
    if getattr(cfg, "mamba_split_proj", False):
        # §Perf/HC4 (zamba2): the fused in_proj splits [z|xs|B|C|dt] at
        # offsets that never align with a 16-way-sharded last axis, so XLA
        # reshards every component per layer (all-to-all + collective-permute
        # observed in the HLO).  Separate, individually-sharded projections
        # make every downstream split collective-free; B/C/dt are tiny and
        # stay replicated.
        return dict(common,
            z_proj=ParamSpec((d, d_inner), ("embed", "ssm_inner")),
            xs_proj=ParamSpec((d, d_inner), ("embed", "ssm_inner")),
            bc_proj=ParamSpec((d, 2 * n), ("embed", None)),
            dt_proj=ParamSpec((d, h), ("embed", None)),
            conv_w_xs=ParamSpec((CONV_K, d_inner), ("conv", "ssm_inner")),
            conv_b_xs=ParamSpec((d_inner,), ("ssm_inner",), init="zeros"),
            conv_w_bc=ParamSpec((CONV_K, 2 * n), ("conv", None)),
            conv_b_bc=ParamSpec((2 * n,), (None,), init="zeros"),
        )
    return dict(common,
        in_proj=ParamSpec((d, 2 * d_inner + 2 * n + h), ("embed", "ssm_inner")),
        conv_w=ParamSpec((CONV_K, conv_dim), ("conv", None)),
        conv_b=ParamSpec((conv_dim,), (None,), init="zeros"),
    )


def _split_proj(cfg, proj):
    d_inner, h, _ = dims(cfg)
    n = cfg.ssm_state
    z, xbc, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc, w, b):
    """Depthwise causal conv1d, kernel CONV_K. xbc: [B, S, C]."""
    pad = jnp.pad(xbc, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :] for i in range(CONV_K))
    return jax.nn.silu(out + b)


def _project(p, cfg, x, return_raw: bool = False):
    """(z, xs_conv, B, C, dt_raw[, raw_xbc]) for either parameterization.

    raw_xbc is the pre-conv [xs|B|C] stream (the decode conv-cache payload).
    x: [B,S,D]."""
    d_inner, h, _ = dims(cfg)
    n = cfg.ssm_state
    if "in_proj" in p:
        proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
        proj = constrain(proj, "batch", None, "act_mlp")
        z, xbc_raw, dt_raw = _split_proj(cfg, proj)
        xbc = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
        xs, B, C = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    else:
        z = constrain(jnp.einsum("bsd,de->bse", x, p["z_proj"]), "batch", None, "act_mlp")
        xs_raw = constrain(jnp.einsum("bsd,de->bse", x, p["xs_proj"]),
                           "batch", None, "act_mlp")
        bc_raw = jnp.einsum("bsd,de->bse", x, p["bc_proj"])
        dt_raw = jnp.einsum("bsd,de->bse", x, p["dt_proj"])
        xs = _causal_conv(xs_raw, p["conv_w_xs"], p["conv_b_xs"])
        bc = _causal_conv(bc_raw, p["conv_w_bc"], p["conv_b_bc"])
        B, C = jnp.split(bc, [n], axis=-1)
        xbc_raw = jnp.concatenate([xs_raw, bc_raw], axis=-1)
    if return_raw:
        return z, xs, B, C, dt_raw, xbc_raw
    return z, xs, B, C, dt_raw


def _ssd_chunked(x, dt, A, B, C, chunk: int):
    """Chunked SSD scan.  x:[B,S,H,P] dt:[B,S,H] A:[H] B,C:[B,S,N]."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    L = min(chunk, s)
    if s % L:
        # pad to whole chunks: dt = 0 leaves the state as it was, so the
        # padded steps change neither y of the real steps nor h_last
        pad = L - s % L
        padded = [jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                  for a in (x, dt, B, C)]
        y, h_last = _ssd_chunked(*padded[:2], A, *padded[2:], chunk)
        return y[:, :s], h_last
    nc = s // L
    xc = x.reshape(b, nc, L, h, p)
    dtc = dt.reshape(b, nc, L, h)
    Bc = B.reshape(b, nc, L, n)
    Cc = C.reshape(b, nc, L, n)

    dA = dtc * A[None, None, None, :]                      # [B,nc,L,H] (<=0)
    cum = jnp.cumsum(dA, axis=2)                           # within-chunk cumulative
    # ---- intra-chunk (quadratic within L) ----
    # att[b,c,h,i,j] = exp(cum_i - cum_j) * (C_i . B_j) * dt_j   for i >= j
    decay = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])   # [B,nc,L,L,H]
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)                        # [B,nc,L,L]
    mask = jnp.tril(jnp.ones((L, L), bool))
    att = decay * cb[..., None] * dtc[:, :, None, :, :]
    att = jnp.where(mask[None, None, :, :, None], att, 0.0)
    att = constrain(att, "batch", None, None, None, "act_heads")
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", att.astype(x.dtype), xc)
    # ---- chunk states ----
    # S_c = sum_j exp(cum_L - cum_j) dt_j B_j (x) x_j   -> [B,nc,H,N,P]
    w_end = jnp.exp(cum[:, :, -1:, :] - cum) * dtc                    # [B,nc,L,H]
    states = jnp.einsum("bcjh,bcjn,bcjhp->bchnp", w_end, Bc, xc.astype(jnp.float32))
    # ---- inter-chunk recurrence ----
    chunk_decay = jnp.exp(cum[:, :, -1, :])                           # [B,nc,H]

    def scan_fn(h_prev, inp):
        st, dec = inp                                                 # [B,H,N,P],[B,H]
        h_new = h_prev * dec[:, :, None, None] + st
        return h_new, h_prev

    init = jnp.zeros((b, h, n, p), jnp.float32)
    h_last, h_before = jax.lax.scan(
        scan_fn, init, (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1))
    )
    h_before = h_before.swapaxes(0, 1)                                # [B,nc,H,N,P]
    # ---- inter-chunk output: y_i += C_i . (exp(cum_i) * h_prev_chunk) ----
    y_inter = jnp.einsum(
        "bcin,bchnp->bcihp", Cc.astype(jnp.float32),
        h_before) * jnp.exp(cum)[..., None]
    y = y_intra.astype(jnp.float32) + y_inter
    return y.reshape(b, s, h, p), h_last


def mamba_block(p: dict, cfg, x: jax.Array, *, chunk: int = 128) -> jax.Array:
    """Full Mamba2 mixer (training / prefill form). x: [B, S, D]."""
    b, s, d = x.shape
    d_inner, h, conv_dim = dims(cfg)
    n = cfg.ssm_state
    z, xs, B, C, dt_raw = _project(p, cfg, x)
    xs = xs.reshape(b, s, h, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, _ = _ssd_chunked(xs, dt, A, B.astype(jnp.float32), C.astype(jnp.float32), chunk)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = layers.rmsnorm(y * jax.nn.silu(z), p["norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return constrain(out, "batch", None, "act_embed")


def mamba_prefill(p: dict, cfg, x: jax.Array, lengths: jax.Array):
    """Mamba2 mixer over right-padded prompts that also returns each row's
    final state, as an unpadded prompt of ``lengths[b]`` tokens leaves it.

    x: [B, S, D]; lengths: int32[B].  ``dt`` is 0 on the pads, so the SSM
    state stops at the last real token; the conv state is the pre-conv
    input of the last ``CONV_K - 1`` real tokens (zeros before the prompt).
    Returns (out [B, S, D], ssm [B, H, N, P] f32, conv [B, CONV_K-1, C])."""
    b, s, d = x.shape
    d_inner, h, conv_dim = dims(cfg)
    z, xs, B, C, dt_raw, xbc_raw = _project(p, cfg, x, return_raw=True)
    xs = xs.reshape(b, s, h, cfg.ssm_head_dim)
    real = jnp.arange(s)[None, :] < lengths[:, None]                  # [B, S]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"])
    y, h_last = _ssd_chunked(xs, dt, A, B.astype(jnp.float32), C.astype(jnp.float32),
                             cfg.ssm_chunk)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = layers.rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    k = CONV_K - 1
    padded = jnp.pad(xbc_raw, ((0, 0), (k, 0), (0, 0)))
    conv = jax.vmap(lambda a, n: jax.lax.dynamic_slice_in_dim(a, n, k, axis=0))(
        padded, lengths)
    return constrain(out, "batch", None, "act_embed"), h_last, conv


def _step_proj(p: dict, cfg, x: jax.Array):
    """One token's projections for either parameterization: (z, pre-conv
    [xs|B|C], dt_raw, conv weight [K, C], conv bias).  x: [B, 1, D]."""
    if "in_proj" in p:
        proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0]       # [B, E]
        z, xbc, dt_raw = _split_proj(cfg, proj)
        return z, xbc, dt_raw, p["conv_w"], p["conv_b"]
    z = jnp.einsum("bsd,de->bse", x, p["z_proj"])[:, 0]
    xs_raw = jnp.einsum("bsd,de->bse", x, p["xs_proj"])[:, 0]
    bc_raw = jnp.einsum("bsd,de->bse", x, p["bc_proj"])[:, 0]
    dt_raw = jnp.einsum("bsd,de->bse", x, p["dt_proj"])[:, 0]
    xbc = jnp.concatenate([xs_raw, bc_raw], axis=-1)
    conv_w = jnp.concatenate([p["conv_w_xs"], p["conv_w_bc"]], axis=-1)
    conv_b = jnp.concatenate([p["conv_b_xs"], p["conv_b_bc"]], axis=-1)
    return z, xbc, dt_raw, conv_w, conv_b


def mamba_decode_step(p: dict, cfg, x: jax.Array, cache: MambaCache):
    """One-token decode. x: [B, 1, D].  State update is O(H*P*N) per token."""
    b, _, d = x.shape
    d_inner, h, conv_dim = dims(cfg)
    n = cfg.ssm_state
    z, xbc, dt_raw, conv_w, conv_b = _step_proj(p, cfg, x)
    # rolling conv buffer: [B, K-1, conv_dim] + current input
    window = jnp.concatenate([cache.conv, xbc[:, None, :]], axis=1)   # [B, K, C]
    conv_out = jnp.einsum("bkc,kc->bc", window, conv_w) + conv_b
    xbc_t = jax.nn.silu(conv_out)
    new_conv = window[:, 1:, :]
    xs, B, C = jnp.split(xbc_t, [d_inner, d_inner + n], axis=-1)
    xs = xs.reshape(b, h, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # [B, H]
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * A[None, :])                                  # [B, H]
    upd = jnp.einsum("bh,bn,bhp->bhnp", dt, B.astype(jnp.float32), xs.astype(jnp.float32))
    h_new = cache.ssm * decay[:, :, None, None] + upd
    y = jnp.einsum("bn,bhnp->bhp", C.astype(jnp.float32), h_new)
    y = y + xs.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(b, d_inner).astype(x.dtype)
    y = layers.rmsnorm(y * jax.nn.silu(z), p["norm"])
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])[:, None, :]
    return out, MambaCache(ssm=h_new, conv=new_conv, length=cache.length + 1)


def mamba_decode_slots(p: dict, cfg, x: jax.Array, ssm_stack, conv_stack, i):
    """One-token Mamba2 step for every slot, against layer ``i`` of per-slot
    state stacks: ``ssm_stack`` f32 [L, B, H, P, N] (the state dim last) and
    ``conv_stack`` [L, B, CONV_K-1, C].  x: [B, 1, D].  Returns (out [B, 1, D],
    ssm_stack, conv_stack) with layer ``i`` advanced.

    The output is taken from the old state, ``C·h_new = decay·(C·h_old) +
    dt·(C·B)·x``, so the new state is only ever written (an elementwise
    update of the stack in place) and never read back."""
    b = x.shape[0]
    d_inner, h, _ = dims(cfg)
    z, xbc, dt_raw, conv_w, conv_b = _step_proj(p, cfg, x)
    window = jnp.concatenate(
        [jax.lax.dynamic_index_in_dim(conv_stack, i, keepdims=False), xbc[:, None, :]], axis=1)
    conv_stack = jax.lax.dynamic_update_index_in_dim(
        conv_stack, window[:, 1:].astype(conv_stack.dtype), i, 0)
    xbc_t = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, conv_w) + conv_b)
    xs, B, C = jnp.split(xbc_t, [d_inner, d_inner + cfg.ssm_state], axis=-1)
    xs = xs.reshape(b, h, cfg.ssm_head_dim).astype(jnp.float32)
    B, C = B.astype(jnp.float32), C.astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # [B, H]
    decay = jnp.exp(dt * -jnp.exp(p["A_log"])[None, :])
    h_old = jax.lax.dynamic_index_in_dim(ssm_stack, i, keepdims=False)  # [B,H,P,N]
    y = (decay[:, :, None] * jnp.einsum("bn,bhpn->bhp", C, h_old)
         + (dt * jnp.sum(C * B, axis=-1, keepdims=True))[:, :, None] * xs
         + xs * p["D"][None, :, None])
    h_new = (h_old * decay[:, :, None, None]
             + (dt[:, :, None] * xs)[..., None] * B[:, None, None, :])
    ssm_stack = jax.lax.dynamic_update_index_in_dim(ssm_stack, h_new, i, 0)
    y = y.reshape(b, d_inner).astype(x.dtype)
    y = layers.rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])[:, None, :]
    return out, ssm_stack, conv_stack


def init_mamba_cache(cfg, batch: int, dtype=jnp.bfloat16) -> MambaCache:
    d_inner, h, conv_dim = dims(cfg)
    return MambaCache(
        ssm=jnp.zeros((batch, h, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),  # [B,H,N,P]
        conv=jnp.zeros((batch, CONV_K - 1, conv_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )


def mamba_cache_axes() -> MambaCache:
    return MambaCache(
        ssm=("cache_batch", "act_heads", None, None),
        conv=("cache_batch", None, None),
        length=(),
    )
