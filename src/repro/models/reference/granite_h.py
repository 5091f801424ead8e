"""Plain float32 reference of the granite-4.0-h (``granitemoehybrid``)
forward pass, for tests against ``models/lm.py``.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, one
sequence at a time, no kernels, no cache, no batching:

- the Mamba2 mixer is the token-by-token recurrence
  ``h_t = exp(dt_t·A)·h_{t-1} + dt_t·B_t ⊗ x_t``, ``y_t = C_t·h_t + D·x_t``
  (not the chunked SSD), after a causal depthwise conv of width 4 and SiLU,
  with the gated RMSNorm ``norm(y·silu(z))``;
- attention is full causal GQA without position embedding, scores times
  ``attention_multiplier``;
- the router is a softmax over the top-k of all ``n_experts`` logits; each
  held expert is computed densely over every token and weighted by its gate
  (zero where the token did not pick it); the shared expert is added once.

Departures from the published description: the layer list is the first
``n_layers`` of ``layer_types``, only the experts held here
(``expert_offset .. expert_offset + experts_held - 1``) add to the MoE output,
as in the program (a chip's share of expert parallelism), and the weights are
the program's own, read in its layout (``lm.model_specs``) and upcast to
float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STACK = {"mamba": "mamba_layers", "attention": "attn_layers"}


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def mamba_mixer(p, x, cfg, state_dtype=jnp.float32):
    """x: [S, D] -> [S, D], the recurrence one token at a time.  The state is
    rounded to ``state_dtype`` after every token (float32: no rounding)."""
    s = x.shape[0]
    d_inner = cfg.ssm_expand * cfg.d_model
    h, n, hp = d_inner // cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_head_dim
    proj = x @ p["in_proj"]
    z, xbc, dt = proj[:, :d_inner], proj[:, d_inner:2 * d_inner + 2 * n], proj[:, 2 * d_inner + 2 * n:]
    k = p["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc], axis=0)
    conv = sum(padded[i:i + s] * p["conv_w"][i] for i in range(k)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(s, h, hp)
    B, C = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # [S, H]
    A = -jnp.exp(p["A_log"])

    def step(hst, inp):
        x_t, dt_t, b_t, c_t = inp
        hst = jnp.exp(dt_t * A)[:, None, None] * hst + (
            dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :])
        hst = hst.astype(state_dtype).astype(jnp.float32)
        return hst, jnp.einsum("n,hnp->hp", c_t, hst) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((h, n, hp)), (xs, dt, B, C))
    y = rmsnorm(y.reshape(s, d_inner) * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"]


def attention_mixer(p, x, cfg):
    """Full causal GQA without positions.  x: [S, D]."""
    s = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, p["wq"])
    k = jnp.einsum("sd,dhk->shk", x, p["wk"])
    v = jnp.einsum("sd,dhk->shk", x, p["wv"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k) * cfg.attention_multiplier
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("shk,hkd->sd", out, p["wo"])


def moe(p, x, cfg):
    """Held experts densely over all tokens, gated by the routing, plus the
    shared expert.  x: [S, D]."""
    logits = x @ p["router"]
    top, ids = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    held = cfg.n_experts_held or cfg.n_experts
    y = swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"])
    for e in range(held):
        g = jnp.sum(jnp.where(ids == cfg.expert_offset + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "state_dtype"))
def layer(stack, i, x, *, kind, cfg, state_dtype=jnp.float32):
    """Layer ``i`` of the ``kind`` stack, its weights upcast here."""
    with jax.default_matmul_precision("highest"):
        p = _f32(jax.tree.map(lambda a: a[i], stack))
        r, eps = cfg.residual_multiplier, cfg.norm_eps
        h = rmsnorm(x, p["ln_mix"], eps)
        if kind == "mamba":
            h = mamba_mixer(p["mamba"], h, cfg, state_dtype)
        else:
            h = attention_mixer(p["attn"], h, cfg)
        x = x + r * h
        return x + r * moe(p["moe"], rmsnorm(x, p["ln_ffn"], cfg.norm_eps), cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _embed(table, tokens, *, cfg):
    return jnp.asarray(table, jnp.float32)[tokens] * cfg.embedding_multiplier


@functools.partial(jax.jit, static_argnames=("cfg",))
def _logits(table, ln_f, x, *, cfg):
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(x, jnp.asarray(ln_f, jnp.float32), cfg.norm_eps)
        return x @ jnp.asarray(table, jnp.float32).T / cfg.logits_scaling


def forward(params, cfg, tokens, state_dtype=jnp.float32) -> jax.Array:
    """Logits [S, V] (float32) of one sequence ``tokens`` [S], layer by
    layer in the published order."""
    x = _embed(params["embed"], jnp.asarray(tokens), cfg=cfg)
    seen = {"mamba": 0, "attention": 0}
    for kind in cfg.layer_types[: cfg.n_layers]:
        x = layer(params[STACK[kind]], np.int32(seen[kind]), x, kind=kind, cfg=cfg,
                  state_dtype=state_dtype)
        seen[kind] += 1
    return _logits(params["embed"], params["ln_f"], x, cfg=cfg)
