"""Mixture-of-Experts FFN: top-k routing + sort-based dropless expert compute.

Expert parallelism (EP): expert weights are sharded over the 'model' mesh axis
(and optionally FSDP-sharded over 'data' for the 1T-param tier).  Tokens stay
sharded over the batch axes and *replicated* over 'model'; each device
computes only its local experts for its token shard and the partial outputs
are combined with a psum over 'model'.  This avoids all-to-all dispatch
entirely — the combine is the same collective a tensor-parallel dense FFN
needs, so MoE costs no extra collective class on this mesh.

Local expert compute is dropless: the token·top_k assignments routed to local
experts are sorted by expert id and fed through ``jax.lax.ragged_dot`` with an
overflow group for non-local assignments (weights padded with one zero
expert), so no capacity factor, no token dropping.

There is an intentional structural echo of the paper here (DESIGN.md §4):
top-k routing is an arbiter — each token raises a "request" and the router
grants up to k expert ports; the sort-by-expert is the TPU idiom for the
grant-vector formation, exactly as prefix-sum rank selection is for the SNN
arbiter.  The MoE layer is where ESAM's event-driven-selection insight
survives at LM scale.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, current_rules
from repro.models import layers, mlp
from repro.models.params import ParamSpec


def moe_specs(cfg) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    # NOTE: expert tensors use 'expert_embed' (never data-sharded) for their
    # d_model dim — the FSDP data shard already lives on 'expert_mlp'.
    return {
        "router": ParamSpec((d, e), ("embed", None), dtype=jnp.float32),
        "w_gate": ParamSpec((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_up": ParamSpec((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_down": ParamSpec((e, f, d), ("experts", "expert_mlp", "expert_embed")),
    }


def _local_expert_ffn(
    x_flat, w_gate, w_up, w_down, expert_ids, gates, n_local, e_offset,
    *, n_experts_total: int, capacity_factor: float = 1.25,
):
    """Capacity-based expert compute for one device's local expert shard.

    EP compute partitioning: each device owns ``n_local`` experts and
    processes at most ``cap`` rows per local expert, where cap is the
    *balanced* per-expert load (T*k / E_total) x capacity_factor — so the
    routed FLOPs split across the model axis instead of being replicated.
    Rows beyond capacity are dropped (standard Switch-style overflow;
    their residual path passes through untouched).

    x_flat: [T, D] local tokens; expert_ids/gates: [T, k] routing decisions;
    w_*: [E_local, ...] local expert weights; e_offset: first local expert id.
    Returns the local partial output [T, D] (zeros for non-local picks).
    """
    t, d = x_flat.shape
    k = expert_ids.shape[1]
    rows = t * k
    cap = max(8, int(np.ceil(rows / n_experts_total * capacity_factor)))
    flat_ids = expert_ids.reshape(-1)                      # [T*k]
    flat_gate = gates.reshape(-1)
    local = (flat_ids >= e_offset) & (flat_ids < e_offset + n_local)
    local_ids = jnp.where(local, flat_ids - e_offset, n_local)
    # position of each row within its expert queue (exclusive running count)
    onehot = (local_ids[:, None] == jnp.arange(n_local)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot              # [T*k, E_local]
    row_pos = jnp.take_along_axis(
        pos, jnp.minimum(local_ids, n_local - 1)[:, None], axis=1)[:, 0]
    keep = local & (row_pos < cap)
    slot = jnp.where(keep, local_ids * cap + row_pos, n_local * cap)  # drop slot
    token_idx = jnp.arange(rows) // k
    # scatter rows into the per-expert capacity buffer (+1 drop slot)
    x_buf = jnp.zeros((n_local * cap + 1, d), x_flat.dtype).at[slot].set(x_flat[token_idx])
    xe = x_buf[: n_local * cap].reshape(n_local, cap, d)
    gate_h = jnp.einsum("ecd,edf->ecf", xe, w_gate)
    up_h = jnp.einsum("ecd,edf->ecf", xe, w_up)
    h = layers.silu(gate_h) * up_h
    out = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(n_local * cap, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)], axis=0)
    # gather back + gate-weighted combine over the k picks
    contrib = out[slot] * (flat_gate * keep)[:, None].astype(out.dtype)
    y = jnp.zeros((t, d), out.dtype).at[token_idx].add(contrib)
    return y


def _capacity_dispatch(x_flat, expert_ids, gates, n_local, e_offset,
                       n_experts_total, capacity_factor):
    """Shared dispatch: scatter local-expert-routed rows into the
    [E_local, cap, D] capacity buffer.  Returns (xe, slot, token_idx,
    gate_scale, cap)."""
    t, d = x_flat.shape
    k = expert_ids.shape[1]
    rows = t * k
    cap = max(8, int(np.ceil(rows / n_experts_total * capacity_factor)))
    flat_ids = expert_ids.reshape(-1)
    flat_gate = gates.reshape(-1)
    local = (flat_ids >= e_offset) & (flat_ids < e_offset + n_local)
    local_ids = jnp.where(local, flat_ids - e_offset, n_local)
    onehot = (local_ids[:, None] == jnp.arange(n_local)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    row_pos = jnp.take_along_axis(
        pos, jnp.minimum(local_ids, n_local - 1)[:, None], axis=1)[:, 0]
    keep = local & (row_pos < cap)
    slot = jnp.where(keep, local_ids * cap + row_pos, n_local * cap)
    token_idx = jnp.arange(rows) // k
    x_buf = jnp.zeros((n_local * cap + 1, d), x_flat.dtype).at[slot].set(x_flat[token_idx])
    xe = x_buf[: n_local * cap].reshape(n_local, cap, d)
    gate_scale = flat_gate * keep
    return xe, slot, token_idx, gate_scale, cap


def _token_gather_expert_ffn(
    x_flat, wg, wu, wd, expert_ids, gates, n_local, e_offset,
    *, n_experts_total: int, capacity_factor: float, pod_fsdp: bool,
):
    """§Perf/HC2: weight-stationary FSDP-MoE — move tokens, not weights.

    Expert shards never leave their device: wg/wu [E_local, D/pod, F/data] and
    wd [E_local, F/data, D/pod] stay resident.  Instead, the (much smaller)
    routed-token capacity buffers are all-gathered across the 'data' axis,
    each device computes its F-slice (and D-slice under pod FSDP) of the
    expert FFN, and a psum_scatter returns each device exactly its own rows.
    Per layer-traversal this moves ~activations instead of ~2 TB of expert
    parameters, and — unlike weight gathering — does NOT multiply with
    gradient-accumulation microbatches (weights stream zero bytes).
    """
    t, d = x_flat.shape
    xe, slot, token_idx, gate_scale, cap = _capacity_dispatch(
        x_flat, expert_ids, gates, n_local, e_offset, n_experts_total,
        capacity_factor)
    # gather every data-shard's capacity buffer: [E_local, R=data*cap, D]
    xg = jax.lax.all_gather(xe, "data", axis=1, tiled=True)
    if pod_fsdp:
        # W1 holds a D-shard: contract x against the matching slice, psum the
        # partial over 'pod' (h is small: [E_local, R, F/data])
        d_shard = wg.shape[1]
        lo = jax.lax.axis_index("pod") * d_shard
        xg_slice = jax.lax.dynamic_slice_in_dim(xg, lo, d_shard, axis=2)
        gate_h = jax.lax.psum(jnp.einsum("erd,edf->erf", xg_slice, wg), "pod")
        up_h = jax.lax.psum(jnp.einsum("erd,edf->erf", xg_slice, wu), "pod")
    else:
        gate_h = jnp.einsum("erd,edf->erf", xg, wg)
        up_h = jnp.einsum("erd,edf->erf", xg, wu)
    h = layers.silu(gate_h) * up_h
    out_part = jnp.einsum("erf,efd->erd", h, wd)   # [E_local, R, D/pod] partial in F
    # reduce over 'data' (sum F-slices) while scattering R back to its home shard
    out = jax.lax.psum_scatter(out_part, "data", scatter_dimension=1, tiled=True)
    if pod_fsdp:                                   # restore full D
        out = jax.lax.all_gather(out, "pod", axis=2, tiled=True)
    out = out.reshape(n_local * cap, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)], axis=0)
    contrib = out[slot] * gate_scale[:, None].astype(out.dtype)
    y = jnp.zeros((t, d), out.dtype).at[token_idx].add(contrib)
    return y


def _held_expert_ffn(x_flat, w_gate, w_up, w_down, expert_ids, gates, e_offset,
                     n_held, first_group=0):
    """The part of the output that experts ``e_offset .. e_offset + n_held - 1``
    give, dropless: every pick of a held expert is computed.  ``w_*`` hold
    groups of experts (``w_gate.shape[0]`` of them); the held experts are
    groups ``first_group .. first_group + n_held - 1``.  Picks of other
    experts sort after the held groups, past the rows ``ragged_dot``
    computes; those rows are unspecified on a TPU and are masked out, not
    multiplied by a zero gate.  The picks go back to their tokens through the
    inverse permutation (a gather, not a scatter-add) and are summed over the
    k picks in float32."""
    t, d = x_flat.shape
    k = expert_ids.shape[1]
    n_groups = w_gate.shape[0]
    flat_ids = expert_ids.reshape(-1) - e_offset
    held = (flat_ids >= 0) & (flat_ids < n_held)
    group = jnp.where(held, first_group + flat_ids, n_groups)
    order = jnp.argsort(group)
    xs = x_flat[order // k]
    group_sizes = jnp.bincount(group, length=n_groups).astype(jnp.int32)
    h = (layers.silu(jax.lax.ragged_dot(xs, w_gate, group_sizes))
         * jax.lax.ragged_dot(xs, w_up, group_sizes))
    out = jax.lax.ragged_dot(h, w_down, group_sizes)        # [T*k, D], sorted
    computed = jnp.arange(t * k) < group_sizes.sum()
    out = jnp.where(computed[:, None], out, 0)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    picks = out[inverse].reshape(t, k, d).astype(jnp.float32)
    w = jnp.where(held, gates.reshape(-1), 0.0).reshape(t, k, 1)
    return jnp.sum(picks * w, axis=1)


def route(p: dict, cfg, x: jax.Array):
    """Router in f32 over all ``cfg.n_experts``: the top-k experts of each
    token and their gates, a softmax over the top-k logits.  x: [B, S, D]."""
    router_logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    top, ids = jax.lax.top_k(router_logits, cfg.top_k)          # [B,S,k]
    return ids, jax.nn.softmax(top, axis=-1)


def held_moe_specs(cfg) -> dict:
    """An expert layer holding ``cfg.experts_held`` of the ``cfg.n_experts``
    experts (ids from ``cfg.expert_offset``), its full-width router, and the
    shared SwiGLU expert every token goes through."""
    e, d, f = cfg.experts_held, cfg.d_model, cfg.d_ff
    return {
        "router": ParamSpec((d, cfg.n_experts), ("embed", None), dtype=jnp.float32),
        "w_gate": ParamSpec((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_up": ParamSpec((e, d, f), ("experts", "expert_embed", "expert_mlp")),
        "w_down": ParamSpec((e, f, d), ("experts", "expert_mlp", "expert_embed")),
        "shared": mlp.mlp_specs(d, cfg.shared_expert_ff),
    }


def held_moe_ffn(p: dict, cfg, x: jax.Array, layer) -> jax.Array:
    """The part of the MoE output that the held experts give, plus the shared
    expert.  Routing is over all experts; on one chip there is no exchange,
    and what the absent experts would add is not stood in for.  x: [B,S,D].

    ``p``'s expert weights are stacked over layers [L, E, ...] and ``layer``
    picks its E: the grouped matmul reads the stack in place (a slice of it,
    taken inside a layer scan, would be copied on every call, since the
    grouped matmul does not fuse with its operands)."""
    b, s, d = x.shape
    ids, gates = route(p, cfg, x)
    ws = [p[k] for k in ("w_gate", "w_up", "w_down")]
    first = layer * ws[0].shape[1]
    y = _held_expert_ffn(
        x.reshape(-1, d), *[w.reshape(-1, *w.shape[2:]) for w in ws],
        ids.reshape(-1, cfg.top_k), gates.reshape(-1, cfg.top_k),
        cfg.expert_offset, cfg.experts_held, first)
    return y.reshape(b, s, d).astype(x.dtype) + mlp.mlp(p["shared"], x)


def moe_ffn(p: dict, cfg, x: jax.Array) -> jax.Array:
    """x: [B, S, D] -> [B, S, D].  Router in fp32; top-k softmax-after-top-k."""
    b, s, d = x.shape
    ids, gates = route(p, cfg, x)
    rules = current_rules()

    if rules is None:
        # single-device functional path (smoke tests): every expert, dropless
        y = _held_expert_ffn(
            x.reshape(-1, d), p["w_gate"], p["w_up"], p["w_down"],
            ids.reshape(-1, cfg.top_k), gates.reshape(-1, cfg.top_k),
            0, cfg.n_experts)
        return y.reshape(b, s, d).astype(x.dtype)

    mesh = rules.mesh
    n_model = mesh.shape["model"]
    n_local = cfg.n_experts // n_model
    batch_spec = rules.rules.get("batch")
    expert_fsdp = rules.rules.get("expert_mlp") is not None

    pod_fsdp = rules.rules.get("expert_embed") == "pod"
    gather_tokens = cfg.moe_impl == "gather_tokens" and expert_fsdp

    def per_device(x_loc, ids_loc, gates_loc, wg, wu, wd):
        e_off = jax.lax.axis_index("model") * n_local
        bl, sl, _ = x_loc.shape
        if gather_tokens:
            y = _token_gather_expert_ffn(
                x_loc.reshape(-1, d), wg, wu, wd,
                ids_loc.reshape(-1, cfg.top_k), gates_loc.reshape(-1, cfg.top_k),
                n_local, e_off, n_experts_total=cfg.n_experts,
                capacity_factor=cfg.capacity_factor, pod_fsdp=pod_fsdp,
            )
        else:
            # weight-gathering FSDP: all-gather the expert shards at use
            # (baseline; traffic = full expert params per traversal)
            if expert_fsdp:
                wg = jax.lax.all_gather(wg, "data", axis=2, tiled=True)
                wu = jax.lax.all_gather(wu, "data", axis=2, tiled=True)
                wd = jax.lax.all_gather(wd, "data", axis=1, tiled=True)
            if pod_fsdp:
                wg = jax.lax.all_gather(wg, "pod", axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, "pod", axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, "pod", axis=2, tiled=True)
            y = _local_expert_ffn(
                x_loc.reshape(-1, d), wg, wu, wd,
                ids_loc.reshape(-1, cfg.top_k), gates_loc.reshape(-1, cfg.top_k),
                n_local, e_off, n_experts_total=cfg.n_experts,
                capacity_factor=cfg.capacity_factor,
            )
        y = jax.lax.psum(y, "model")          # combine expert partials (EP)
        return y.reshape(bl, sl, d)

    w_axis = ("experts", "expert_embed", "expert_mlp")
    spec_w = rules.spec(w_axis)
    spec_wd = rules.spec(("experts", "expert_mlp", "expert_embed"))
    spec_x = P(batch_spec, None, None)
    spec_r = P(batch_spec, None, None)
    from repro import compat

    y = compat.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec_x, spec_r, spec_r, spec_w, spec_w, spec_wd),
        out_specs=spec_x,
        check=False,
    )(x, ids, gates, p["w_gate"], p["w_up"], p["w_down"])
    return constrain(y, "batch", None, "act_embed")


def moe_aux_loss(router_logits: jax.Array, ids: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style load-balancing loss (mean fraction * mean prob per expert)."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    counts = jax.nn.one_hot(ids[..., 0], n_experts).mean(axis=(0, 1))
    return n_experts * jnp.sum(counts * probs.mean(axis=(0, 1)))
