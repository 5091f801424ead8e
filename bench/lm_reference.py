"""Plain float32 reference of the granite-4.0-h (``granitemoehybrid``) LM,
for the comparison that decides ``correct`` in the LM cells.

It imports nothing of the program under test.  The sizes come from the
configuration file (the published ``config.json`` keys); the weights are the
benchmark's own draw in the published layout and under the published names
(``lm_weights``), drawn again one layer at a time and upcast to float32, so
that the reference needs no copy of the program's weights.  Everything runs
under ``jax.default_matmul_precision("highest")``, one sequence at a time,
with no cache and no batching:

- Mamba2: ``in_proj`` split into z, x B C and dt; causal depthwise conv
  (width ``mamba_d_conv``) and SiLU, then the token-by-token recurrence
  ``h_t = exp(dt_t·A)·h_{t-1} + dt_t·B_t ⊗ x_t``, ``y_t = C_t·h_t + D·x_t``,
  then ``rmsnorm(y·silu(z))`` and ``out_proj``;
- attention: full causal GQA with no position embedding, scores times
  ``attention_multiplier``;
- MoE: a softmax over the top ``num_experts_per_tok`` of all
  ``published.num_local_experts`` router logits; each held expert's fused
  ``input_linear`` split into gate and up, computed densely over every token
  and weighted by its gate; the shared expert once;
- each layer ``x + r·mixer(norm(x))`` then ``x + r·(moe + shared)(norm(x))``,
  embeddings times ``embedding_multiplier``, logits over ``logits_scaling``.

Departures from the published model, as in the program: the first
``num_hidden_layers`` of ``layer_types``, only the held experts
(``expert_offset`` onwards, ``num_local_experts`` of them) add to the MoE
output, and the vocabulary is the slice ``0 .. vocab_size - 1``.

``Precision`` rounds what the configuration states one step lower, for the
controls: the Mamba2 state to bfloat16 after every token (the configuration
holds it in float32), every activation that enters a matmul to float8 e4m3
(the configuration's activations are bfloat16) and every weight matrix to
float8 e4m3 with one scale per tensor (the configuration's weights are
bfloat16).  The default rounds nothing.  Rounding is
``jax.lax.reduce_precision`` (e4m3 with IEEE-style range, largest 240): a
round trip through a narrower dtype is a pair of converts that XLA on a TPU
may drop, since it allows excess precision.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


#: (exponent bits, mantissa bits) of the formats a control rounds to
FORMATS = {"bfloat16": (8, 7), "float8_e4m3": (4, 3)}


class Precision(NamedTuple):
    state: Optional[str] = None        # "bfloat16": the SSM state
    act: Optional[str] = None          # "float8_e4m3": activations into matmuls
    weight: Optional[str] = None       # "float8_e4m3": weight matrices


EXACT = Precision()


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the reference reads of the configuration file, by the names it
    uses below."""

    d_model: int
    d_inner: int
    ssm_head_dim: int
    ssm_state: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    shared_ff: int
    top_k: int
    n_experts_held: int
    expert_offset: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    norm_eps: float
    layer_types: tuple


def sizes(config: dict) -> Sizes:
    return Sizes(
        d_model=int(config["hidden_size"]),
        d_inner=int(config["mamba_expand"]) * int(config["hidden_size"]),
        ssm_head_dim=int(config["mamba_d_head"]), ssm_state=int(config["mamba_d_state"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        d_ff=int(config["intermediate_size"]),
        shared_ff=int(config["shared_intermediate_size"]),
        top_k=int(config["num_experts_per_tok"]),
        n_experts_held=int(config["num_local_experts"]),
        expert_offset=int(config["expert_offset"]),
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=float(config["rms_norm_eps"]),
        layer_types=tuple(config["layer_types"][: int(config["num_hidden_layers"])]))


def _round(x, fmt):
    """x rounded to the format ``fmt`` names (None: x)."""
    if fmt is None:
        return x
    e, m = FORMATS[fmt]
    return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)


def _largest(fmt) -> float:
    e, m = FORMATS[fmt]
    return (2.0 - 2.0 ** -m) * 2.0 ** (2 ** (e - 1) - 1)


def _weights(w: dict, fmt) -> dict:
    """Upcast to float32; with ``fmt``, every matrix first rounded to it at
    one scale per tensor (its largest magnitude at the format's largest)."""
    def one(a):
        a = jnp.asarray(a, jnp.float32)
        if fmt is None or a.ndim < 2:
            return a
        s = jnp.max(jnp.abs(a)) / _largest(fmt)
        return _round(a / s, fmt) * s

    return jax.tree.map(one, w)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def linear(x, w, act=None):
    """x @ w.T for a published [out, in] weight."""
    return _round(x, act) @ w.T


def swiglu(x, fused_in, w_out, act=None):
    """The published fused MLP: ``input_linear`` [gate | up, in]."""
    gate, up = jnp.split(linear(x, fused_in, act), 2, axis=-1)
    return linear(jax.nn.silu(gate) * up, w_out, act)


def mamba_mixer(w, x, n_real, sz, prec=EXACT):
    """x: [S, D] -> ([S, D], the state after token ``n_real - 1`` [H, N, P]),
    one token at a time."""
    s = x.shape[0]
    d_inner, n, hp = sz.d_inner, sz.ssm_state, sz.ssm_head_dim
    h = d_inner // hp
    proj = linear(x, w["mamba.in_proj.weight"], prec.act)
    z, xbc, dt = proj[:, :d_inner], proj[:, d_inner:2 * d_inner + 2 * n], proj[:, 2 * d_inner + 2 * n:]
    conv_w = w["mamba.conv1d.weight"][:, 0, :]                    # [C, K]
    k = conv_w.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], axis=0)
    conv = sum(padded[i:i + s] * conv_w[:, i] for i in range(k)) + w["mamba.conv1d.bias"]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(s, h, hp)
    B, C = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])
    A = -jnp.exp(w["mamba.A_log"])
    real = jnp.arange(s) < n_real

    def step(hst, inp):
        x_t, dt_t, b_t, c_t, r_t = inp
        new = jnp.exp(dt_t * A)[:, None, None] * hst + (
            dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :])
        new = _round(new, prec.state)
        y_t = jnp.einsum("n,hnp->hp", c_t, new) + w["mamba.D"][:, None] * x_t
        return jnp.where(r_t, new, hst), y_t

    last, y = jax.lax.scan(step, jnp.zeros((h, n, hp)), (xs, dt, B, C, real))
    y = rmsnorm(y.reshape(s, d_inner) * jax.nn.silu(z), w["mamba.norm.weight"], sz.norm_eps)
    return linear(y, w["mamba.out_proj.weight"], prec.act), last


def attention_mixer(w, x, sz, prec=EXACT):
    s, hd = x.shape[0], sz.d_model // sz.n_heads
    q = linear(x, w["self_attn.q_proj.weight"], prec.act).reshape(s, sz.n_heads, hd)
    k = linear(x, w["self_attn.k_proj.weight"], prec.act).reshape(s, sz.n_kv_heads, hd)
    v = linear(x, w["self_attn.v_proj.weight"], prec.act).reshape(s, sz.n_kv_heads, hd)
    group = sz.n_heads // sz.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", _round(q, prec.act), _round(k, prec.act))
    scores = scores * sz.attention_multiplier
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, axis=-1), _round(v, prec.act))
    return linear(out.reshape(s, -1), w["self_attn.o_proj.weight"], prec.act)


def moe(w, x, sz, prec=EXACT):
    logits = x @ w["block_sparse_moe.router.layer.weight"].T
    top, ids = jax.lax.top_k(logits, sz.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    y = swiglu(x, w["shared_mlp.input_linear.weight"], w["shared_mlp.output_linear.weight"],
               prec.act)
    w_in, w_out = (w["block_sparse_moe.input_linear.weight"],
                   w["block_sparse_moe.output_linear.weight"])
    for e in range(sz.n_experts_held):
        g = jnp.sum(jnp.where(ids == sz.expert_offset + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * swiglu(x, w_in[e], w_out[e], prec.act)
    return y


@functools.partial(jax.jit, static_argnames=("kind", "sz", "prec"))
def layer(w, x, n_real, *, kind, sz, prec=EXACT):
    """One layer of ``kind`` on x [S, D], its published tensors ``w`` upcast
    here.  Returns (x, the Mamba2 state after token ``n_real - 1``, or
    None)."""
    with jax.default_matmul_precision("highest"):
        w = _weights(w, prec.weight)
        r = sz.residual_multiplier
        h = rmsnorm(x, w["input_layernorm.weight"], sz.norm_eps)
        state = None
        if kind == "mamba":
            h, state = mamba_mixer(w, h, n_real, sz, prec)
        else:
            h = attention_mixer(w, h, sz, prec)
        x = x + r * h
        f = rmsnorm(x, w["post_attention_layernorm.weight"], sz.norm_eps)
        return x + r * moe(w, f, sz, prec), state


@functools.partial(jax.jit, static_argnames=("sz",))
def _embed(table, tokens, *, sz):
    return jnp.asarray(table, jnp.float32)[tokens] * sz.embedding_multiplier


@functools.partial(jax.jit, static_argnames=("sz",))
def _logits(table, final_norm, x, *, sz):
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(x, jnp.asarray(final_norm, jnp.float32), sz.norm_eps)
        return x @ jnp.asarray(table, jnp.float32).T / sz.logits_scaling


def run(draw_layer: Callable[[int], dict], embed, final_norm, sz: Sizes, seqs: list,
        positions: list, precisions=(EXACT,), width: Optional[int] = None) -> dict:
    """Teacher-forced reference of sequences ``seqs`` (int32 arrays; each is
    padded at its end to ``width``, by default the longest, so that each
    layer kind compiles once a width).  Layer ``i``'s weights come from
    ``draw_layer(i)`` once, for every sequence and precision.

    Returns ``{precision: [(logits [len(positions[j]), V] at the positions
    given, Mamba2 states [L_mamba, H, N, P] after the sequence's last
    token) for each sequence j]}`` as numpy arrays."""
    width = width or max((s.size for s in seqs), default=1)
    toks = np.zeros((len(seqs), width), np.int32)
    for j, s in enumerate(seqs):
        toks[j, : s.size] = s
    xs = {p: [_embed(embed, jnp.asarray(t), sz=sz) for t in toks] for p in precisions}
    states = {p: [[] for _ in seqs] for p in precisions}
    for i, kind in enumerate(sz.layer_types):
        w = draw_layer(i)
        for p in precisions:
            for j, s in enumerate(seqs):
                xs[p][j], st = layer(w, xs[p][j], np.int32(s.size), kind=kind, sz=sz, prec=p)
                if st is not None:
                    states[p][j].append(st)
        del w
    return {p: [(np.asarray(_logits(embed, final_norm, xs[p][j][np.asarray(positions[j])],
                                    sz=sz)),
                 np.asarray(jnp.stack(states[p][j])) if states[p][j] else np.zeros(0))
                for j in range(len(seqs))]
            for p in precisions}


def rel_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: max |got - want| over the vocabulary / RMS of ``want``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rms = np.sqrt(np.mean(want * want, axis=-1))
    return np.max(np.abs(got - want), axis=-1) / rms


def state_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per Mamba2 layer: ||got - want|| / ||want|| over the layer's state."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    axes = tuple(range(1, want.ndim))
    return (np.sqrt(np.sum((got - want) ** 2, axis=axes))
            / np.sqrt(np.sum(want * want, axis=axes)))
