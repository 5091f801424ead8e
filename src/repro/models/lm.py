"""Unified language model: specs + train/prefill/decode entry points for every
assigned architecture family.

Layer stacks use ``jax.lax.scan`` over stacked parameters (compile-time and
HLO-size critical for the 61-layer/384-expert configs); activation
checkpointing wraps the scan body.  Family dispatch:

  dense / vlm      pre-norm GQA attn + SwiGLU MLP                   (scan)
  moe              pre-norm GQA attn + MoE FFN                      (scan)
  hybrid (zamba2)  groups of ``shared_attn_period`` Mamba2 blocks,
                   one *shared-weight* attn+MLP block after each    (scan over
                   group; inner scan over the group's mamba layers)
  hybrid_moe       the published per-layer list cfg.layer_types of
  (granite-4.0-h)  Mamba2 / NoPE-GQA mixers, each followed by a held-expert
                   MoE FFN + shared expert, muP scalars           (one scan
                   per run of layers of one kind, over its kind's stack)
  xlstm            mLSTM blocks with sLSTM at cfg.slstm_layers      (unrolled;
                   12 layers, HLO stays small)
  encdec           bidirectional encoder + causal decoder w/ cross-attn

Serving keeps per-slot caches (``Caches.lengths`` int32[B]) for every
family: ``prefill_rows`` fills them from right-padded prompts,
``insert_slot`` writes one prompt's state into a slot of a larger cache, and
``decode_step`` advances every slot by one token.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import layers, mlp, moe, params as pm, ssm, xlstm
from repro.models.params import ParamSpec


# --------------------------------------------------------------------- #
# spec helpers
# --------------------------------------------------------------------- #
def _stack_specs(n: int, specs):
    """Prepend a scan-stacked 'layers' dim to every spec in the tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale, s.dtype),
        specs,
        is_leaf=pm.is_spec,
    )


def _block_specs(cfg) -> dict:
    """One standard transformer block (attn + ffn + norms)."""
    s = {
        "ln_attn": layers.rmsnorm_spec(cfg.d_model),
        "attn": attn.attn_specs(cfg),
        "ln_ffn": layers.rmsnorm_spec(cfg.d_model),
    }
    if cfg.family == "moe":
        s["ffn"] = moe.moe_specs(cfg)
    else:
        s["ffn"] = mlp.mlp_specs(cfg.d_model, cfg.d_ff)
    return s


def model_specs(cfg) -> dict:
    specs: dict[str, Any] = {
        "embed": layers.embed_spec(cfg.vocab_size, cfg.d_model, cfg.tied_embeddings),
        "ln_f": layers.rmsnorm_spec(cfg.d_model),
    }
    if not cfg.tied_embeddings:
        specs["unembed"] = ParamSpec(
            (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="scaled", scale=0.02
        )
    if cfg.family in ("dense", "vlm", "moe"):
        specs["blocks"] = _stack_specs(cfg.n_layers, _block_specs(cfg))
    elif cfg.family == "hybrid":
        period = cfg.shared_attn_period
        assert cfg.n_layers % period == 0
        groups = cfg.n_layers // period
        mamba_layer = {"pre_ln": layers.rmsnorm_spec(cfg.d_model),
                       "mamba": ssm.mamba_specs(cfg)}
        specs["mamba"] = _stack_specs(groups, _stack_specs(period, mamba_layer))
        specs["shared"] = _block_specs(dataclasses.replace(cfg, family="dense"))
    elif cfg.family == "hybrid_moe":
        n_m, n_a = _kind_counts(cfg)
        specs["mamba_layers"] = _stack_specs(n_m, _hm_layer_specs(cfg, "mamba"))
        specs["attn_layers"] = _stack_specs(n_a, _hm_layer_specs(cfg, "attention"))
    elif cfg.family == "xlstm":
        blocks = []
        for i in range(cfg.n_layers):
            if i in cfg.slstm_layers:
                blocks.append({"kind_slstm": xlstm.slstm_specs(cfg),
                               "ln": layers.rmsnorm_spec(cfg.d_model)})
            else:
                blocks.append({"kind_mlstm": xlstm.mlstm_specs(cfg),
                               "ln": layers.rmsnorm_spec(cfg.d_model)})
        specs["blocks"] = blocks
    elif cfg.family == "encdec":
        enc_cfg = dataclasses.replace(cfg, family="dense")
        enc_block = {
            "ln_attn": layers.rmsnorm_spec(cfg.d_model),
            "attn": attn.attn_specs(enc_cfg),
            "ln_ffn": layers.rmsnorm_spec(cfg.d_model),
            "ffn": mlp.mlp_specs(cfg.d_model, cfg.d_ff),
        }
        dec_block = dict(enc_block)
        dec_block["ln_cross"] = layers.rmsnorm_spec(cfg.d_model)
        dec_block["cross"] = attn.cross_attention_specs(enc_cfg)
        specs["encoder"] = _stack_specs(cfg.enc_layers, enc_block)
        specs["decoder"] = _stack_specs(cfg.dec_layers, dec_block)
        # audio frontend is a stub: inputs arrive as precomputed frame
        # embeddings (DESIGN.md §4); only a projection is learned here.
        specs["frontend_proj"] = ParamSpec((cfg.d_model, cfg.d_model), ("embed", "embed"))
    else:
        raise ValueError(cfg.family)
    return specs


# --------------------------------------------------------------------- #
# block applications
# --------------------------------------------------------------------- #
def _remat_wrap(cfg, fn):
    """Wrap a scan body / block fn with the configured remat policy."""
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:  # "full"
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def _apply_block(bp, cfg, x, *, window=None):
    h = attn.self_attention(bp["attn"], cfg, layers.rmsnorm(x, bp["ln_attn"]),
                            causal=True, window=window)
    x = x + h
    ffn_in = layers.rmsnorm(x, bp["ln_ffn"])
    if cfg.family == "moe":
        x = x + moe.moe_ffn(bp["ffn"], cfg, ffn_in)
    else:
        x = x + mlp.mlp(bp["ffn"], ffn_in)
    return x


def _scan_blocks(stacked, cfg, x, *, window=None):
    def body(carry, bp):
        y = _apply_block(bp, cfg, carry, window=window)
        return y, None

    x, _ = jax.lax.scan(_remat_wrap(cfg, body), x, stacked)
    return x


def _hybrid_forward(p, cfg, x, *, window=None):
    def group_body(carry, gp):
        def mamba_body(c, lp):
            return c + ssm.mamba_block(lp["mamba"], cfg,
                                       layers.rmsnorm(c, lp["pre_ln"])), None

        y, _ = jax.lax.scan(mamba_body, carry, gp)
        y = _apply_block(p["shared"], cfg, y, window=window)   # shared weights
        return y, None

    x, _ = jax.lax.scan(_remat_wrap(cfg, group_body), x, p["mamba"])
    return x


def _xlstm_forward(p, cfg, x):
    def one_block(bp, h_in):
        h = layers.rmsnorm(h_in, bp["ln"])
        if "kind_slstm" in bp:
            return h_in + xlstm.slstm_block(bp["kind_slstm"], cfg, h)
        return h_in + xlstm.mlstm_block(bp["kind_mlstm"], cfg, h)

    one_block = _remat_wrap(cfg, one_block)
    for bp in p["blocks"]:
        x = one_block(bp, x)
    return x


# --------------------------------------------------------------------- #
# hybrid_moe: a published list of Mamba2 / attention layers, each + MoE
# --------------------------------------------------------------------- #
_KIND_STACK = {"mamba": "mamba_layers", "attention": "attn_layers"}


def _layer_runs(cfg) -> list:
    """[(kind, index of the run's first layer in its kind's stack, layers)]
    in the published order of ``cfg.layer_types[:cfg.n_layers]``."""
    runs, seen = [], {"mamba": 0, "attention": 0}
    for kind, grp in itertools.groupby(cfg.layer_types[: cfg.n_layers]):
        n = len(list(grp))
        runs.append((kind, seen[kind], n))
        seen[kind] += n
    return runs


def _kind_counts(cfg) -> tuple[int, int]:
    kinds = cfg.layer_types[: cfg.n_layers]
    assert len(kinds) == cfg.n_layers and set(kinds) <= set(_KIND_STACK), kinds
    return kinds.count("mamba"), kinds.count("attention")


def _hm_layer_specs(cfg, kind: str) -> dict:
    if kind == "mamba":
        m = ssm.mamba_specs(cfg)
        # the config gives no initialisation: Mamba2's defaults for A and dt
        m["A_log"] = dataclasses.replace(m["A_log"], init="mamba_a_log")
        m["dt_bias"] = dataclasses.replace(m["dt_bias"], init="mamba_dt_bias")
        mixer = {"mamba": m}
    else:
        mixer = {"attn": attn.attn_specs(cfg)}
    return {"ln_mix": layers.rmsnorm_spec(cfg.d_model), **mixer,
            "ln_ffn": layers.rmsnorm_spec(cfg.d_model),
            "moe": moe.held_moe_specs(cfg)}


def _at(stack, i):
    """Layer ``i`` of a stacked parameter or cache tree."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def _hm_layer(stack, i, cfg, x, mixer):
    """Layer ``i`` of ``stack``: x + r·mixer(norm(x)), then
    x + r·(moe(norm(x)) + shared(norm(x))); ``mixer(lp, h) -> (out, state)``.
    The experts are handed to the MoE as the whole stack with the layer's
    index, so that their matmul reads them in place."""
    lp = _at(stack, i)
    r = cfg.residual_multiplier
    h, state = mixer(lp, layers.rmsnorm(x, lp["ln_mix"], cfg.norm_eps))
    x = x + h * r
    f = layers.rmsnorm(x, lp["ln_ffn"], cfg.norm_eps)
    experts = {k: stack["moe"][k] for k in ("w_gate", "w_up", "w_down")}
    return x + moe.held_moe_ffn(dict(lp["moe"], **experts), cfg, f, layer=i) * r, state


def _hm_prompt(params, cfg, tokens, lengths, remat: bool = False):
    """Every layer over right-padded prompts.  Returns the last hidden
    states [B, S, D] and each layer's state as an unpadded prompt of
    ``lengths[b]`` tokens leaves it: (ssm, conv) stacked over the Mamba2
    layers and (k, v) over the attention layers, each in its stack's order."""
    x = _embed_in(params, cfg, tokens)
    mixers = {
        "mamba": lambda lp, h: (lambda o, st, cv: (o, (st, cv)))(
            *ssm.mamba_prefill(lp["mamba"], cfg, h, lengths)),
        "attention": lambda lp, h: (lambda o, k, v: (o, (k, v)))(
            *attn.prefill_attention_rows(lp["attn"], cfg, h, lengths)),
    }
    states = {"mamba": [], "attention": []}
    for kind, first, n in _layer_runs(cfg):
        stack = params[_KIND_STACK[kind]]

        def body(c, i, kind=kind, stack=stack):
            return _hm_layer(stack, i, cfg, c, mixers[kind])

        if remat:
            body = _remat_wrap(cfg, body)
        x, st = jax.lax.scan(body, x, jnp.arange(first, first + n))
        states[kind].append(st)
    cat = {k: jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *v)
           for k, v in states.items() if v}
    return x, cat.get("mamba"), cat.get("attention")


def _hm_decode(params, cfg, x, caches):
    """One token for every slot through every layer, in published order.
    The caches' stacks ride in the scan carry and are updated in place: a
    Mamba2 layer's whole state, an attention layer's one new K/V row per
    slot."""
    lengths = caches.lengths
    rows = jnp.arange(x.shape[0])

    def mamba_mix(lp, h, st, i):
        out, *st = ssm.mamba_decode_slots(lp["mamba"], cfg, h, *st, i)
        return out, tuple(st)

    def attn_mix(lp, h, st, i):
        out, k, v = attn.decode_attention_slots(lp["attn"], cfg, h, _at(st[0], i),
                                                _at(st[1], i), lengths)
        return out, tuple(a.at[i, rows, lengths].set(n.astype(a.dtype), mode="drop")
                          for a, n in zip(st, (k, v)))

    state = {"mamba": (caches.mamba.ssm, caches.mamba.conv),
             "attention": (caches.attn.k, caches.attn.v)}
    for kind, first, n in _layer_runs(cfg):
        stack = params[_KIND_STACK[kind]]
        mix = mamba_mix if kind == "mamba" else attn_mix

        def body(carry, i, stack=stack, mix=mix):
            c, st = carry
            return _hm_layer(stack, i, cfg, c, lambda lp, h: mix(lp, h, st, i)), None

        (x, state[kind]), _ = jax.lax.scan(body, (x, state[kind]),
                                          jnp.arange(first, first + n))
    return x, Caches(mamba=ssm.MambaCache(*state["mamba"], length=()),
                     attn=attn.KVCache(*state["attention"], length=()),
                     lengths=lengths + 1)


# --------------------------------------------------------------------- #
# top-level entry points
# --------------------------------------------------------------------- #
def _act_dtype(params, cfg):
    """bf16 activations; hybrid_moe computes in its weights' dtype."""
    return params["embed"].dtype if cfg.family == "hybrid_moe" else jnp.bfloat16


def _embed_in(params, cfg, tokens):
    x = layers.embed_lookup(params["embed"], tokens).astype(_act_dtype(params, cfg))
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return constrain(x, "batch", None, "act_embed")


def _logits_out(params, cfg, x):
    x = layers.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"] if cfg.tied_embeddings else params["unembed"]
    logits = layers.unembed(x, table)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return constrain(logits, "batch", None, "vocab")


def forward_train(params, cfg, batch) -> jax.Array:
    """Teacher-forced logits. batch: {'tokens': [B,S]} (+ 'src_frames' for
    encdec audio: [B, S_src, D] precomputed frame embeddings)."""
    if cfg.is_encdec:
        return _encdec_forward(params, cfg, batch)
    x = _embed_in(params, cfg, batch["tokens"])
    window = cfg.window if (cfg.window and batch["tokens"].shape[1] > cfg.window) else None
    if cfg.family in ("dense", "vlm", "moe"):
        x = _scan_blocks(params["blocks"], cfg, x, window=window)
    elif cfg.family == "hybrid":
        x = _hybrid_forward(params, cfg, x, window=window)
    elif cfg.family == "hybrid_moe":
        b, s = batch["tokens"].shape
        x, _, _ = _hm_prompt(params, cfg, batch["tokens"], jnp.full((b,), s, jnp.int32),
                             remat=True)
    elif cfg.family == "xlstm":
        x = _xlstm_forward(params, cfg, x)
    else:
        raise ValueError(cfg.family)
    return _logits_out(params, cfg, x)


def _encdec_forward(params, cfg, batch):
    frames = batch["src_frames"].astype(jnp.bfloat16)          # [B, S_src, D] stub
    mem = jnp.einsum("bsd,de->bse", frames, params["frontend_proj"])
    mem = constrain(mem, "batch", None, "act_embed")

    def enc_body(carry, bp):
        h = attn.self_attention(bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]),
                                causal=False)
        y = carry + h
        y = y + mlp.mlp(bp["ffn"], layers.rmsnorm(y, bp["ln_ffn"]))
        return y, None

    def dec_body(carry, bp):
        h = attn.self_attention(bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]),
                                causal=True)
        y = carry + h
        y = y + attn.cross_attention(bp["cross"], cfg, layers.rmsnorm(y, bp["ln_cross"]), mem)
        y = y + mlp.mlp(bp["ffn"], layers.rmsnorm(y, bp["ln_ffn"]))
        return y, None

    enc_body = _remat_wrap(cfg, enc_body)
    dec_body = _remat_wrap(cfg, dec_body)
    mem, _ = jax.lax.scan(enc_body, mem, params["encoder"])
    x = _embed_in(params, cfg, batch["tokens"])
    x, _ = jax.lax.scan(dec_body, x, params["decoder"])
    return _logits_out(params, cfg, x)


def loss_fn(params, cfg, batch) -> jax.Array:
    logits = forward_train(params, cfg, batch)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()


# ===================================================================== #
# Serving: prefill + single-token decode with per-family caches
# ===================================================================== #
class Caches(NamedTuple):
    """Family-polymorphic cache container (unused fields are () placeholders)."""

    attn: Any = ()      # stacked KVCache [L, ...]        (dense/moe/vlm; encdec dec self)
    cross: Any = ()     # (k, v) stacked [L, B, T, KV, hd] (encdec; per slot
                        # also the source lengths int32[B])
    mamba: Any = ()     # stacked MambaCache [G, P, ...]   (hybrid); per slot (hybrid_moe)
    shared: Any = ()    # stacked KVCache [G, ...]         (hybrid shared blocks)
    xl: Any = ()        # tuple of per-block caches        (xlstm)
    lengths: Any = ()   # int32[B] tokens per slot         (per-slot caches)


def init_slot_caches(cfg, batch: int, s_max: int, dtype=jnp.bfloat16) -> Caches:
    """Empty per-slot caches: ``batch`` slots of ``s_max`` positions each.
    KV rows are ``dtype`` [L, B, S_max, KV, hd] (the activations' dtype);
    Mamba2 SSM state is f32 [L, B, H, P, N], the state dim last so that a
    TPU tile's 128 lanes hold N (with P = 64 last, the state would be
    re-laid out, twice its size, on every decode step); conv state
    ``dtype`` [L, B, CONV_K-1, C]; xLSTM keeps its per-block recurrent
    state [B, ...]; encdec keeps its decoder's self-attention KV and, per
    slot, the cross-attention K/V of its source and the source's length."""
    def kv(n):
        shape = (n, batch, s_max, cfg.n_kv_heads, cfg.hd)
        return attn.KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), ())

    def mamba(n):
        _, h, conv_dim = ssm.dims(cfg)
        return ssm.MambaCache(
            ssm=jnp.zeros((n, batch, h, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            conv=jnp.zeros((n, batch, ssm.CONV_K - 1, conv_dim), dtype), length=())

    out = Caches(lengths=jnp.zeros((batch,), jnp.int32))
    if cfg.family in ("dense", "vlm", "moe"):
        return out._replace(attn=kv(cfg.n_layers))
    if cfg.family == "hybrid_moe":
        n_m, n_a = _kind_counts(cfg)
        return out._replace(attn=kv(n_a), mamba=mamba(n_m))
    if cfg.family == "hybrid":
        return out._replace(mamba=mamba(cfg.n_layers),
                            shared=kv(cfg.n_layers // cfg.shared_attn_period))
    if cfg.family == "xlstm":
        # a buffer of its own for every leaf: the engine donates them
        return out._replace(xl=jax.tree.map(lambda a: jnp.array(a, copy=True),
                                            init_caches(cfg, batch, s_max).xl))
    if cfg.family == "encdec":
        cross = kv(cfg.dec_layers)
        return out._replace(attn=kv(cfg.dec_layers), cross=(
            cross.k, cross.v, jnp.zeros((batch,), jnp.int32)))
    raise ValueError(cfg.family)


def _rows_where(real, new, old):
    """Per row b of every cache leaf [B, ...]: ``new`` where ``real[b]``."""
    return jax.tree.map(
        lambda n, o: jnp.where(real.reshape(real.shape + (1,) * (n.ndim - 1)), n, o)
        if n.ndim else n, new, old)


def _hybrid_rows(params, cfg, tokens, lengths):
    """zamba2 over right-padded prompts: the last hidden states, the Mamba2
    (ssm [L, B, H, N, P], conv) states of every layer and the shared
    blocks' (k, v) [G, B, S, KV, hd]."""
    sp = params["shared"]

    def group_body(carry, gp):
        def mamba_body(c, lp):
            out, st, conv = ssm.mamba_prefill(lp["mamba"], cfg,
                                              layers.rmsnorm(c, lp["pre_ln"]), lengths)
            return c + out, (st, conv)

        y, st = jax.lax.scan(mamba_body, carry, gp)
        h, k, v = attn.prefill_attention_rows(sp["attn"], cfg,
                                              layers.rmsnorm(y, sp["ln_attn"]), lengths)
        y = y + h
        y = y + mlp.mlp(sp["ffn"], layers.rmsnorm(y, sp["ln_ffn"]))
        return y, (st, (k, v))

    x, (st, kv) = jax.lax.scan(group_body, _embed_in(params, cfg, tokens), params["mamba"])
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), st)
    return x, flat, kv


def _xlstm_step(params, cfg, x, xl):
    """One token through every xLSTM block.  x: [B, 1, D]."""
    new = list(xl)
    for i, bp in enumerate(params["blocks"]):
        h = layers.rmsnorm(x, bp["ln"])
        if "kind_slstm" in bp:
            out, new[i] = xlstm.slstm_decode_step(bp["kind_slstm"], cfg, h, xl[i])
        else:
            out, new[i] = xlstm.mlstm_decode_step(bp["kind_mlstm"], cfg, h, xl[i])
        x = x + out
    return x, tuple(new)


def _xlstm_rows(params, cfg, tokens, lengths):
    """xLSTM over right-padded prompts, one token at a time through the
    recurrent step; a row's state stops at its last real token."""
    b, s = tokens.shape

    def body(carry, t):
        xl, last = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        x, new = _xlstm_step(params, cfg, _embed_in(params, cfg, tok), xl)
        logits = _logits_out(params, cfg, x)[:, 0].astype(jnp.float32)
        last = jnp.where((t == lengths - 1)[:, None], logits, last)
        return (_rows_where(t < lengths, new, xl), last), None

    init = (init_caches(cfg, b, s).xl, jnp.zeros((b, cfg.vocab_size), jnp.float32))
    (xl, last), _ = jax.lax.scan(body, init, jnp.arange(s))
    return last, Caches(xl=xl, lengths=lengths.astype(jnp.int32))


def _encdec_rows(params, cfg, tokens, lengths):
    """Encoder-decoder over right-padded prompts.  The audio frontend is a
    stub: each request's source is zero frames of its prompt's length.
    Returns the decoder's last hidden states, its self-attention (k, v) and
    the cross-attention (k, v) of the source, rows past a length zero."""
    b, s = tokens.shape
    frames = jnp.zeros((b, s, cfg.d_model), jnp.bfloat16)
    mem = jnp.einsum("bsd,de->bse", frames, params["frontend_proj"])
    real = (jnp.arange(s)[None, :] < lengths[:, None])[..., None, None]

    def enc_body(carry, bp):
        h = attn.self_attention(bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]),
                                causal=False, key_lengths=lengths)
        y = carry + h
        return y + mlp.mlp(bp["ffn"], layers.rmsnorm(y, bp["ln_ffn"])), None

    mem, _ = jax.lax.scan(enc_body, mem, params["encoder"])

    def dec_body(carry, bp):
        h, k, v = attn.prefill_attention_rows(bp["attn"], cfg,
                                              layers.rmsnorm(carry, bp["ln_attn"]), lengths)
        y = carry + h
        ck, cv = (jnp.where(real, jnp.einsum("btd,dhk->bthk", mem, bp["cross"][w]), 0
                            ).astype(jnp.bfloat16) for w in ("wk", "wv"))
        y = y + attn.cross_attention(bp["cross"], cfg, layers.rmsnorm(y, bp["ln_cross"]),
                                     None, memory_kv=(ck, cv), memory_lengths=lengths)
        y = y + mlp.mlp(bp["ffn"], layers.rmsnorm(y, bp["ln_ffn"]))
        return y, (k, v, ck, cv)

    x, (k, v, ck, cv) = jax.lax.scan(dec_body, _embed_in(params, cfg, tokens),
                                     params["decoder"])
    return x, (k, v), (ck, cv)


def prefill_rows(params, cfg, tokens, lengths, capacity: Optional[int] = None):
    """Right-padded prompts into fresh per-slot caches.

    tokens: int32[B, S], row b real up to ``lengths[b]``.  Returns the
    logits at each row's last real position [B, V] and per-slot caches of
    ``capacity`` (>= S) positions holding exactly what unpadded prompts
    leave: K/V rows past a prompt are zero, recurrent state stops at its
    last real token."""
    b, s = tokens.shape
    capacity = max(s, capacity or s)
    lengths = lengths.astype(jnp.int32)
    if cfg.family == "xlstm":
        return _xlstm_rows(params, cfg, tokens, lengths)

    def pad(a):
        return jnp.pad(a, [(0, 0), (0, 0), (0, capacity - s), (0, 0), (0, 0)])

    caches = Caches(lengths=lengths)
    if cfg.family == "hybrid_moe":
        x, (st, conv), (k, v) = _hm_prompt(params, cfg, tokens, lengths)
        caches = caches._replace(
            attn=attn.KVCache(pad(k), pad(v), ()),
            mamba=ssm.MambaCache(ssm=st.swapaxes(-1, -2), conv=conv, length=()))
    elif cfg.family == "hybrid":
        x, (st, conv), (k, v) = _hybrid_rows(params, cfg, tokens, lengths)
        caches = caches._replace(
            shared=attn.KVCache(pad(k), pad(v), ()),
            mamba=ssm.MambaCache(ssm=st.swapaxes(-1, -2), conv=conv, length=()))
    elif cfg.family == "encdec":
        x, (k, v), (ck, cv) = _encdec_rows(params, cfg, tokens, lengths)
        caches = caches._replace(attn=attn.KVCache(pad(k), pad(v), ()),
                                 cross=(pad(ck), pad(cv), lengths))
    elif cfg.family in ("dense", "vlm", "moe"):
        def body(carry, bp):
            h, k, v = attn.prefill_attention_rows(
                bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]), lengths)
            y = carry + h
            ffn_in = layers.rmsnorm(y, bp["ln_ffn"])
            if cfg.family == "moe":
                y = y + moe.moe_ffn(bp["ffn"], cfg, ffn_in)
            else:
                y = y + mlp.mlp(bp["ffn"], ffn_in)
            return y, (k, v)

        x, (k, v) = jax.lax.scan(body, _embed_in(params, cfg, tokens), params["blocks"])
        caches = caches._replace(attn=attn.KVCache(pad(k), pad(v), ()))
    else:
        raise ValueError(cfg.family)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return _logits_out(params, cfg, last)[:, 0], caches


def insert_slot(caches: Caches, one: Caches, slot) -> Caches:
    """Write the one-row caches ``one`` (from ``prefill_rows``) into slot
    ``slot`` of ``caches``: its K/V rows ``0 .. S-1``, its recurrent state,
    its source's cross K/V, and its length.  Nothing of another slot is
    touched."""
    def put(axis):
        def one_leaf(big, small):
            if not big.ndim:
                return big
            start = [0] * big.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(big, small.astype(big.dtype), start)
        return one_leaf

    fields = {f: jax.tree.map(put(1), getattr(caches, f), getattr(one, f))
              for f in ("attn", "mamba", "shared")}
    fields["xl"] = jax.tree.map(put(0), caches.xl, one.xl)
    if caches.cross:
        fields["cross"] = tuple(put(a)(big, small) for a, big, small
                                in zip((1, 1, 0), caches.cross, one.cross))
    return caches._replace(lengths=caches.lengths.at[slot].set(one.lengths[0]),
                           **fields)


def _write_rows(cache_l, new, lengths):
    """Each slot's new K or V row [B, KV, hd] at its row ``lengths[b]``."""
    rows = jnp.arange(cache_l.shape[0])
    return cache_l.at[rows, lengths].set(new.astype(cache_l.dtype), mode="drop")


def _hybrid_decode(params, cfg, x, caches):
    """zamba2, one token for every slot: each group's Mamba2 layers against
    the per-slot state stacks, then the shared block against the group's
    per-slot KV."""
    lengths, period, sp = caches.lengths, cfg.shared_attn_period, params["shared"]

    def group_body(carry, xs):
        gp, g, k_l, v_l = xs

        def mamba_body(c, xs2):
            y, st, conv = c
            lp, j = xs2
            out, st, conv = ssm.mamba_decode_slots(
                lp["mamba"], cfg, layers.rmsnorm(y, lp["pre_ln"]), st, conv, g * period + j)
            return (y + out, st, conv), None

        (y, st, conv), _ = jax.lax.scan(mamba_body, carry, (gp, jnp.arange(period)))
        h, k, v = attn.decode_attention_slots(sp["attn"], cfg, layers.rmsnorm(y, sp["ln_attn"]),
                                              k_l, v_l, lengths)
        y = y + h
        y = y + mlp.mlp(sp["ffn"], layers.rmsnorm(y, sp["ln_ffn"]))
        return (y, st, conv), (_write_rows(k_l, k, lengths), _write_rows(v_l, v, lengths))

    groups = cfg.n_layers // period
    (x, st, conv), (k, v) = jax.lax.scan(
        group_body, (x, caches.mamba.ssm, caches.mamba.conv),
        (params["mamba"], jnp.arange(groups), caches.shared.k, caches.shared.v))
    return x, caches._replace(mamba=ssm.MambaCache(st, conv, ()),
                              shared=attn.KVCache(k, v, ()))


def _decode_slots(params, cfg, tokens, caches: Caches):
    """decode_step over per-slot caches (``caches.lengths`` int32[B])."""
    x = _embed_in(params, cfg, tokens)
    lengths = caches.lengths
    if cfg.family == "hybrid_moe":
        x, caches = _hm_decode(params, cfg, x, caches)
        return _logits_out(params, cfg, x)[:, 0], caches
    if cfg.family == "hybrid":
        x, caches = _hybrid_decode(params, cfg, x, caches)
    elif cfg.family == "xlstm":
        x, xl = _xlstm_step(params, cfg, x, caches.xl)
        caches = caches._replace(xl=xl)
    else:
        encdec = cfg.family == "encdec"

        def body(carry, xs):
            bp, k_l, v_l = xs[:3]
            h, k, v = attn.decode_attention_slots(
                bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]), k_l, v_l, lengths)
            y = carry + h
            if encdec:
                y = y + attn.cross_attention(
                    bp["cross"], cfg, layers.rmsnorm(y, bp["ln_cross"]), None,
                    memory_kv=xs[3:], memory_lengths=caches.cross[2])
            ffn_in = layers.rmsnorm(y, bp["ln_ffn"])
            if cfg.family == "moe":
                y = y + moe.moe_ffn(bp["ffn"], cfg, ffn_in)
            else:
                y = y + mlp.mlp(bp["ffn"], ffn_in)
            return y, (_write_rows(k_l, k, lengths), _write_rows(v_l, v, lengths))

        stack = params["decoder"] if encdec else params["blocks"]
        xs = (stack, caches.attn.k, caches.attn.v) + (caches.cross[:2] if encdec else ())
        x, (k, v) = jax.lax.scan(body, x, xs)
        caches = caches._replace(attn=attn.KVCache(k, v, ()))
    return _logits_out(params, cfg, x)[:, 0], caches._replace(lengths=lengths + 1)


def _decode_window(cfg, s_max: int):
    """Sliding window active for long-context decode on windowed archs."""
    if cfg.window and s_max > cfg.window:
        return cfg.window
    return None


def init_caches(cfg, batch: int, s_max: int, src_len: Optional[int] = None) -> Caches:
    if cfg.family == "hybrid_moe":
        return init_slot_caches(cfg, batch, s_max)
    if cfg.family in ("dense", "vlm", "moe"):
        win = _decode_window(cfg, s_max)
        c = attn.init_cache(cfg, batch, min(s_max, win or s_max))
        stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), c)
        return Caches(attn=attn.KVCache(*stacked))
    if cfg.family == "hybrid":
        period = cfg.shared_attn_period
        groups = cfg.n_layers // period
        mc = ssm.init_mamba_cache(cfg, batch)
        mstack = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (groups, period) + a.shape), mc)
        win = _decode_window(cfg, s_max)
        sc = attn.init_cache(cfg, batch, min(s_max, win or s_max))
        sstack = jax.tree.map(lambda a: jnp.broadcast_to(a, (groups,) + a.shape), sc)
        return Caches(mamba=ssm.MambaCache(*mstack), shared=attn.KVCache(*sstack))
    if cfg.family == "xlstm":
        xl = []
        for i in range(cfg.n_layers):
            if i in cfg.slstm_layers:
                xl.append(xlstm.init_slstm_cache(cfg, batch))
            else:
                xl.append(xlstm.init_mlstm_cache(cfg, batch))
        return Caches(xl=tuple(xl))
    if cfg.family == "encdec":
        # s_max = decoder (target) cache capacity; src_len = encoder memory len
        src = src_len if src_len is not None else s_max
        c = attn.init_cache(cfg, batch, s_max)
        stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.dec_layers,) + a.shape), c)
        hd = cfg.hd
        cross_k = jnp.zeros((cfg.dec_layers, batch, src, cfg.n_kv_heads, hd), jnp.bfloat16)
        return Caches(attn=attn.KVCache(*stacked), cross=(cross_k, cross_k))
    raise ValueError(cfg.family)


def cache_axes(cfg) -> Caches:
    """Logical-axis tree matching init_caches (leading 'layers'/group dims)."""
    def stack(ax_tuple, extra=1):
        return tuple(("layers",) * extra) + ax_tuple if isinstance(ax_tuple, tuple) else ax_tuple

    if cfg.family in ("dense", "vlm", "moe"):
        base = attn.cache_axes()
        return Caches(attn=attn.KVCache(
            k=("layers",) + base.k, v=("layers",) + base.v, length=()))
    if cfg.family == "hybrid_moe":
        base, mb = attn.cache_axes(), ssm.mamba_cache_axes()
        return Caches(
            attn=attn.KVCache(k=("layers",) + base.k, v=("layers",) + base.v, length=()),
            mamba=ssm.MambaCache(ssm=("layers",) + mb.ssm, conv=("layers",) + mb.conv,
                                 length=()),
            lengths=("cache_batch",))
    if cfg.family == "hybrid":
        mb = ssm.mamba_cache_axes()
        mstack = ssm.MambaCache(
            ssm=("layers", "layers") + mb.ssm,
            conv=("layers", "layers") + mb.conv, length=())
        base = attn.cache_axes()
        sstack = attn.KVCache(k=("layers",) + base.k, v=("layers",) + base.v, length=())
        return Caches(mamba=mstack, shared=sstack)
    if cfg.family == "xlstm":
        xl = []
        for i in range(cfg.n_layers):
            xl.append(xlstm.slstm_cache_axes() if i in cfg.slstm_layers
                      else xlstm.mlstm_cache_axes())
        return Caches(xl=tuple(xl))
    if cfg.family == "encdec":
        base = attn.cache_axes()
        ax = ("layers", "cache_batch", "cache_seq", "cache_kv", "head_dim")
        return Caches(attn=attn.KVCache(k=("layers",) + base.k, v=("layers",) + base.v,
                                        length=()), cross=(ax, ax))
    raise ValueError(cfg.family)


def prefill(params, cfg, batch, cache_len: Optional[int] = None) -> tuple[jax.Array, Caches]:
    """Run the full prompt; returns (last-token logits [B, V], filled caches).

    cache_len: KV-cache capacity (>= prompt length); pass prompt + max_new
    when the caches will be decoded into afterwards."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    capacity = max(s, cache_len or s)
    src_len = batch["src_frames"].shape[1] if cfg.is_encdec else None
    if cfg.family == "hybrid_moe":
        return prefill_rows(params, cfg, tokens, jnp.full((b,), s, jnp.int32), capacity)
    caches = init_caches(cfg, b, capacity, src_len=src_len)
    win = cfg.window if (cfg.window and s > cfg.window) else None
    if cfg.family in ("dense", "vlm", "moe"):
        x = _embed_in(params, cfg, tokens)

        def body(carry, xs):
            bp, cache_l = xs
            h, new_c = attn.prefill_attention(
                bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]), cache_l, window=win)
            y = carry + h
            ffn_in = layers.rmsnorm(y, bp["ln_ffn"])
            if cfg.family == "moe":
                y = y + moe.moe_ffn(bp["ffn"], cfg, ffn_in)
            else:
                y = y + mlp.mlp(bp["ffn"], ffn_in)
            return y, new_c

        x, new_attn = jax.lax.scan(body, x, (params["blocks"], caches.attn))
        logits = _logits_out(params, cfg, x[:, -1:, :])[:, 0]
        return logits, Caches(attn=new_attn)
    if cfg.family == "hybrid":
        return _hybrid_prefill(params, cfg, tokens, caches, win)
    if cfg.family == "xlstm":
        return _xlstm_prefill(params, cfg, tokens, caches)
    if cfg.family == "encdec":
        return _encdec_prefill(params, cfg, batch, caches)
    raise ValueError(cfg.family)


def _hybrid_prefill(params, cfg, tokens, caches, win):
    x = _embed_in(params, cfg, tokens)
    b, s = tokens.shape

    def group_body(carry, xs):
        gp, mcache_g, scache_g = xs

        def mamba_body(c, xs2):
            lp, mcache_l = xs2
            h = layers.rmsnorm(c, lp["pre_ln"])
            # prefill = run the chunked form AND capture the final state
            out, final_state = _mamba_prefill_block(lp["mamba"], cfg, h)
            new_cache = ssm.MambaCache(
                ssm=final_state[0], conv=final_state[1],
                length=jnp.asarray(s, jnp.int32))
            return c + out, new_cache

        y, new_mcaches = jax.lax.scan(mamba_body, carry, (gp, mcache_g))
        h, new_scache = attn.prefill_attention(
            params["shared"]["attn"], cfg,
            layers.rmsnorm(y, params["shared"]["ln_attn"]), scache_g, window=win)
        y = y + h
        y = y + mlp.mlp(params["shared"]["ffn"], layers.rmsnorm(y, params["shared"]["ln_ffn"]))
        return y, (new_mcaches, new_scache)

    x, (new_m, new_s) = jax.lax.scan(group_body, x, (params["mamba"], caches.mamba, caches.shared))
    logits = _logits_out(params, cfg, x[:, -1:, :])[:, 0]
    return logits, Caches(mamba=new_m, shared=new_s)


def _mamba_prefill_block(p, cfg, x, chunk: int = 128):
    """Mamba block that also returns (final ssm state, final conv window)."""
    b, s, d = x.shape
    d_inner, h, conv_dim = ssm.dims(cfg)
    n = cfg.ssm_state
    z, xs_, B, C, dt_raw, xbc_raw = ssm._project(p, cfg, x, return_raw=True)
    conv_tail = xbc_raw[:, -(ssm.CONV_K - 1):, :]              # final conv window
    xs_ = xs_.reshape(b, s, h, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, h_last = ssm._ssd_chunked(xs_, dt, A, B.astype(jnp.float32), C.astype(jnp.float32), chunk)
    y = y + xs_.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = layers.rmsnorm(y * jax.nn.silu(z), p["norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    # h_last: [B,H,N,P] — already the MambaCache layout
    return constrain(out, "batch", None, "act_embed"), (h_last, conv_tail)


def _xlstm_prefill(params, cfg, tokens, caches):
    """Chunkwise prefill with exact recurrent-state capture per block."""
    x = _embed_in(params, cfg, tokens)
    b, s, _ = x.shape
    length = jnp.asarray(s, jnp.int32)
    new_caches = list(caches.xl)
    for i, bp in enumerate(params["blocks"]):
        h = layers.rmsnorm(x, bp["ln"])
        if "kind_slstm" in bp:
            out, (c, n, hst, m) = xlstm.slstm_block(bp["kind_slstm"], cfg, h,
                                                    return_state=True)
            new_caches[i] = xlstm.SLstmCache(c=c, n=n, h=hst, m=m, length=length)
        else:
            out, (C, n, m) = xlstm.mlstm_block(bp["kind_mlstm"], cfg, h,
                                               return_state=True)
            new_caches[i] = xlstm.MLstmCache(C=C, n=n, m=m, length=length)
        x = x + out
    logits = _logits_out(params, cfg, x[:, -1:, :])[:, 0]
    return logits, Caches(xl=tuple(new_caches))


def _encdec_prefill(params, cfg, batch, caches):
    frames = batch["src_frames"].astype(jnp.bfloat16)
    mem = jnp.einsum("bsd,de->bse", frames, params["frontend_proj"])

    def enc_body(carry, bp):
        h = attn.self_attention(bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]),
                                causal=False)
        y = carry + h
        y = y + mlp.mlp(bp["ffn"], layers.rmsnorm(y, bp["ln_ffn"]))
        return y, None

    mem, _ = jax.lax.scan(enc_body, mem, params["encoder"])
    x = _embed_in(params, cfg, batch["tokens"])

    def dec_body(carry, xs):
        bp, cache_l = xs
        h, new_c = attn.prefill_attention(
            bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]), cache_l)
        y = carry + h
        ck = jnp.einsum("btd,dhk->bthk", mem, bp["cross"]["wk"]).astype(jnp.bfloat16)
        cv = jnp.einsum("btd,dhk->bthk", mem, bp["cross"]["wv"]).astype(jnp.bfloat16)
        y = y + attn.cross_attention(bp["cross"], cfg, layers.rmsnorm(y, bp["ln_cross"]),
                                     mem, memory_kv=(ck, cv))
        y = y + mlp.mlp(bp["ffn"], layers.rmsnorm(y, bp["ln_ffn"]))
        return y, (new_c, ck, cv)

    x, (new_attn, cks, cvs) = jax.lax.scan(dec_body, x, (params["decoder"], caches.attn))
    logits = _logits_out(params, cfg, x[:, -1:, :])[:, 0]
    return logits, Caches(attn=new_attn, cross=(cks, cvs))


def decode_step(params, cfg, tokens, caches: Caches) -> tuple[jax.Array, Caches]:
    """One new token per sequence. tokens: [B, 1] -> (logits [B, V], caches).
    Per-slot caches (``caches.lengths`` set) may hold slots of different
    lengths."""
    if not isinstance(caches.lengths, tuple):
        return _decode_slots(params, cfg, tokens, caches)
    x = _embed_in(params, cfg, tokens)
    if cfg.family in ("dense", "vlm", "moe"):
        win = _decode_window(cfg, int(caches.attn.k.shape[2]) + 1) if cfg.window else None

        def body(carry, xs):
            bp, cache_l = xs
            h, new_c = attn.decode_attention(
                bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]), cache_l,
                window=cfg.window if cfg.window else None)
            y = carry + h
            ffn_in = layers.rmsnorm(y, bp["ln_ffn"])
            if cfg.family == "moe":
                y = y + moe.moe_ffn(bp["ffn"], cfg, ffn_in)
            else:
                y = y + mlp.mlp(bp["ffn"], ffn_in)
            return y, new_c

        x, new_attn = jax.lax.scan(body, x, (params["blocks"], caches.attn))
        return _logits_out(params, cfg, x)[:, 0], Caches(attn=new_attn)
    if cfg.family == "hybrid":
        def group_body(carry, xs):
            gp, mcache_g, scache_g = xs

            def mamba_body(c, xs2):
                lp, mcache_l = xs2
                h = layers.rmsnorm(c, lp["pre_ln"])
                out, new_c = ssm.mamba_decode_step(lp["mamba"], cfg, h, mcache_l)
                return c + out, new_c

            y, new_m = jax.lax.scan(mamba_body, carry, (gp, mcache_g))
            h, new_s = attn.decode_attention(
                params["shared"]["attn"], cfg,
                layers.rmsnorm(y, params["shared"]["ln_attn"]), scache_g,
                window=cfg.window)
            y = y + h
            y = y + mlp.mlp(params["shared"]["ffn"],
                            layers.rmsnorm(y, params["shared"]["ln_ffn"]))
            return y, (new_m, new_s)

        x, (new_m, new_s) = jax.lax.scan(
            group_body, x, (params["mamba"], caches.mamba, caches.shared))
        return _logits_out(params, cfg, x)[:, 0], Caches(mamba=new_m, shared=new_s)
    if cfg.family == "xlstm":
        x, xl = _xlstm_step(params, cfg, x, caches.xl)
        return _logits_out(params, cfg, x)[:, 0], Caches(xl=xl)
    if cfg.family == "encdec":
        def dec_body(carry, xs):
            bp, cache_l, ck, cv = xs
            h, new_c = attn.decode_attention(
                bp["attn"], cfg, layers.rmsnorm(carry, bp["ln_attn"]), cache_l)
            y = carry + h
            y = y + attn.cross_attention(bp["cross"], cfg,
                                         layers.rmsnorm(y, bp["ln_cross"]),
                                         None, memory_kv=(ck, cv))
            y = y + mlp.mlp(bp["ffn"], layers.rmsnorm(y, bp["ln_ffn"]))
            return y, new_c

        cks, cvs = caches.cross
        x, new_attn = jax.lax.scan(dec_body, x, (params["decoder"], caches.attn, cks, cvs))
        return _logits_out(params, cfg, x)[:, 0], Caches(attn=new_attn, cross=caches.cross)
    raise ValueError(cfg.family)
