"""Work and byte counts of the LM's prefill calls and decode steps, from the
configuration's shapes and the run's token counts, whatever implements them.

FLOPs are two per multiply-accumulate the model needs: the projections and
experts a token goes through, attention over the positions before it, and
the SSM recurrence.  The MoE part counts the experts held here: a token
picks ``top_k`` of the router's ``n_experts``, so on average
``top_k · held / n_experts`` of its picks are computed on this chip.

The fewest bytes a call can move: every held weight once (at 32 slots an
expert gets no token in a decode step with probability
(1 - 1/72)^320 ≈ 1.1%, so every held expert is counted in every step), the
Mamba2 SSM and conv state of each sequence read and written once, and the
KV rows read up to each sequence's length and written once per new token.
Prefill writes its KV rows and its final state.
"""

from __future__ import annotations

import dataclasses

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Shapes:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_inner: int
    ssm_heads: int
    ssm_state: int
    conv: int
    expert_ff: int
    shared_ff: int
    n_experts: int
    held: int
    top_k: int
    vocab: int
    n_mamba: int
    n_attn: int


def shapes(config: dict) -> Shapes:
    """The LM shapes of a configuration file (published key names)."""
    d = int(config["hidden_size"])
    kinds = config["layer_types"][: int(config["num_hidden_layers"])]
    d_inner = int(config["mamba_expand"]) * d
    return Shapes(
        d_model=d, n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=d // int(config["num_attention_heads"]), d_inner=d_inner,
        ssm_heads=d_inner // int(config["mamba_d_head"]),
        ssm_state=int(config["mamba_d_state"]), conv=int(config["mamba_d_conv"]),
        expert_ff=int(config["intermediate_size"]),
        shared_ff=int(config["shared_intermediate_size"]),
        n_experts=int(config["published"]["num_local_experts"]),
        held=int(config["num_local_experts"]), top_k=int(config["num_experts_per_tok"]),
        vocab=int(config["vocab_size"]), n_mamba=kinds.count("mamba"),
        n_attn=kinds.count("attention"))


def _mamba_params(s: Shapes) -> int:
    conv_dim = s.d_inner + 2 * s.ssm_state
    in_proj = s.d_model * (2 * s.d_inner + 2 * s.ssm_state + s.ssm_heads)
    return in_proj + s.d_inner * s.d_model + s.conv * conv_dim


def _attn_params(s: Shapes) -> int:
    return s.d_model * s.head_dim * (2 * s.n_heads + 2 * s.n_kv_heads)


def _ffn_params(s: Shapes, experts: float) -> float:
    return (s.d_model * s.n_experts + 3 * s.d_model * s.shared_ff
            + experts * 3 * s.d_model * s.expert_ff)


def macs_per_token(s: Shapes) -> float:
    """Multiply-accumulates of one token through the held share of every
    layer and the logits, without attention over earlier positions."""
    picked = s.top_k * s.held / s.n_experts
    ssm = 3 * s.ssm_heads * s.ssm_state * (s.d_inner // s.ssm_heads)
    per_layer = _ffn_params(s, picked)
    return (s.n_mamba * (_mamba_params(s) + ssm + per_layer)
            + s.n_attn * (_attn_params(s) + per_layer) + s.vocab * s.d_model)


def attention_macs(s: Shapes, positions: float) -> float:
    """Scores and values of one token against ``positions`` earlier ones
    (itself included), over every attention layer."""
    return s.n_attn * 2 * s.n_heads * s.head_dim * positions


def weight_bytes(s: Shapes) -> int:
    """Every weight held here, bf16 (router and norms in f32 are a rounding
    error and counted as bf16)."""
    layer = _ffn_params(s, s.held) + 2 * s.d_model
    return BF16 * int(s.n_mamba * (_mamba_params(s) + layer)
                      + s.n_attn * (_attn_params(s) + layer) + s.vocab * s.d_model)


def state_bytes(s: Shapes) -> int:
    """One sequence's Mamba2 state: f32 SSM state and bf16 conv window."""
    per_layer = (F32 * s.d_inner * s.ssm_state
                 + BF16 * (s.conv - 1) * (s.d_inner + 2 * s.ssm_state))
    return s.n_mamba * per_layer


def kv_bytes_per_position(s: Shapes) -> int:
    return BF16 * 2 * s.n_kv_heads * s.head_dim * s.n_attn


def prefill_work(s: Shapes, n: int) -> tuple[float, float]:
    """(flops, bytes) of prefilling one prompt of ``n`` tokens."""
    flops = 2 * (n * macs_per_token(s) + attention_macs(s, n * (n + 1) / 2))
    nbytes = weight_bytes(s) + state_bytes(s) + n * kv_bytes_per_position(s)
    return flops, nbytes


def decode_work(s: Shapes, steps: int, live: int, positions: int) -> tuple[float, float]:
    """(flops, bytes) of ``steps`` decode steps that made ``live`` tokens
    between them for sequences holding ``positions`` tokens before each
    step, summed over the steps."""
    flops = 2 * (live * macs_per_token(s) + attention_macs(s, positions + live))
    nbytes = (steps * weight_bytes(s) + 2 * live * state_bytes(s)
              + (positions + live) * kv_bytes_per_position(s))
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time a call could take on a chip of ``peak`` (bf16)."""
    t_ops = flops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def window_least_time(s: Shapes, lm: dict, peak: dict) -> tuple[float, str]:
    """Σ least time of the window's prefill calls and decode steps, and the
    bound that sets most of it.  ``lm`` is the driver's record: the prompt
    lengths prefilled, and the decode steps with the tokens they made and
    the positions the live sequences held.  Every decode step is bound by
    memory at these shapes (32 tokens x about 5 GFLOP against some 8 GB of
    weights), so the steps' least times add up to that of their summed
    counts."""
    per_bound = {"compute": 0.0, "memory": 0.0}
    for n in lm["prefill_lengths"]:
        t, b = least_time(*prefill_work(s, n), peak)
        per_bound[b] += t
    if lm["decode_steps"]:
        t, b = least_time(*decode_work(s, lm["decode_steps"], lm["decode_tokens"],
                                       lm["decode_positions"]), peak)
        per_bound[b] += t
    total = per_bound["compute"] + per_bound["memory"]
    return total, max(per_bound, key=per_bound.get)


def window_macs(s: Shapes, lm: dict) -> float:
    """Multiply-accumulates of the prompt and generated tokens the window
    processed (each generated token once, by the decode step that made it)."""
    prompts = sum(n * macs_per_token(s) + attention_macs(s, n * (n + 1) / 2)
                  for n in lm["prefill_lengths"])
    return prompts + lm["decode_tokens"] * macs_per_token(s) + attention_macs(
        s, lm["decode_positions"] + lm["decode_tokens"])
