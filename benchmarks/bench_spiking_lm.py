"""Beyond-paper ablation: ESAM-mode (SpikingLinear) FFN inside a tiny LM.

Trains two 2-layer LMs on the synthetic token task — one with a dense FFN,
one with the binary event-driven FFN + top-p arbitration — and reports the
quality gap, the measured event rate, and what that activity would cost on
the ESAM 4R tile per the calibrated cost model (cycles = ceil(events/ports)).
This quantifies where the paper's mechanism could slot into an LM stack and
what it would save/cost.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import spiking
from repro.core.esam import cost_model as cm
from repro.models.params import ParamSpec
import repro.models.params as pm

VOCAB, D, FF, S, B = 256, 64, 128, 32, 16
PORTS = 32  # top-p arbiter limit (per-token event budget)


def _specs(mode: str) -> dict:
    s = {
        # O(1) embeddings: the binary path spikes on sign(x) (scale-free), the
        # dense path needs unit-scale activations for comparable optimization
        "embed": ParamSpec((VOCAB, D), (None, None), init="scaled", scale=0.5,
                           dtype=jnp.float32),
        "w_attn": ParamSpec((D, D), (None, None), dtype=jnp.float32),
        "ln": ParamSpec((D,), (None,), init="ones", dtype=jnp.float32),
    }
    if mode == "dense":
        s["ffn_up"] = ParamSpec((D, FF), (None, None), dtype=jnp.float32)
    else:
        s.update({f"ffn_{k}": v for k, v in spiking.spiking_linear_specs(D, FF).items()})
    s["ffn_down"] = ParamSpec((FF, D), (None, None), dtype=jnp.float32)
    s["unembed"] = ParamSpec((D, VOCAB), (None, None), dtype=jnp.float32)
    return s


def _forward(params, tokens, mode):
    x = params["embed"][tokens]
    # single mixing layer (cumulative mean attention proxy keeps this tiny)
    ctx = jnp.cumsum(x, axis=1) / (jnp.arange(x.shape[1])[None, :, None] + 1)
    x = x + ctx @ params["w_attn"]
    xn = x * params["ln"]
    if mode == "dense":
        h = jax.nn.gelu(xn @ params["ffn_up"])
        rate = jnp.zeros(())
    else:
        h = spiking.spiking_linear(
            {"w": params["ffn_w"], "b": params["ffn_b"]}, xn, ports=PORTS)
        rate = spiking.event_rate(xn, ports=PORTS)
    x = x + h @ params["ffn_down"]
    return x @ params["unembed"], rate


def _train(mode: str, steps: int = 250):
    key = jax.random.PRNGKey(0)
    params = pm.init(_specs(mode), key)
    rng = np.random.default_rng(0)
    # token task with copy structure (predictable from context)
    base = rng.integers(0, VOCAB, size=(B, S + 1))
    base[:, S // 2:] = base[:, : S + 1 - S // 2]

    def loss_fn(p, toks):
        logits, rate = _forward(p, toks[:, :-1], mode)
        lp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(lp, toks[:, 1:, None], axis=2).mean()
        return nll, rate

    @jax.jit
    def step(p, toks):
        (l, rate), g = jax.value_and_grad(loss_fn, has_aux=True)(p, toks)
        p = jax.tree.map(lambda a, b: a - 0.05 * b, p, g)
        return p, l, rate

    toks = jnp.asarray(base)
    l = rate = None
    for _ in range(steps):
        params, l, rate = step(params, toks)
    return float(l), float(rate)


def run():
    # warmup=0: whole multi-step training runs (too expensive to run twice;
    # compile amortizes across the steps).
    us_d, (loss_dense, _) = time_call(lambda: _train("dense"), repeats=1, warmup=0)
    us_s, (loss_spike, rate) = time_call(lambda: _train("spiking"), repeats=1, warmup=0)
    # ESAM hardware cost of the measured activity for one token's FFN MAC:
    # events = rate * D rows; a 4R tile drains them in ceil(events/4) cycles.
    events = rate * D
    spec = cm.cell_spec(4)
    cycles = float(np.ceil(events / spec.ports))
    t_ns = cycles * spec.clock_ns
    e_pj = events * spec.e_read_pj * (FF // 128 + 1)
    emit("spiking_lm_dense", us_d,
         f"final_loss={loss_dense:.3f}(single-batch memorization task)")
    emit("spiking_lm_esam_ffn", us_s,
         f"final_loss={loss_spike:.3f};event_rate={rate:.3f};ports={PORTS};"
         f"esam4R_cycles_per_token={cycles:.0f};t_ns={t_ns:.1f};e_pj={e_pj:.2f};"
         f"note=binary FFN trains through STE and its activity maps onto the "
         f"4R tile at ~{cycles:.0f} cycles/token")


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
