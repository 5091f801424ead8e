"""granite-4.0-h-small (hybrid_moe): the slot engine against the plain
float32 reference, padded prefill state, the expert-share layer, the
engine's one pull per decode step, and the published parameter count.

Tolerances: the engine runs the SMOKE widths in float32 here, so it differs
from the reference only by summation order (chunked SSD against the
token-by-token recurrence, sorted ragged expert rows against dense masked
experts, padded against unpadded softmax rows): relative gaps of 1e-6 to
1e-5 of the logits' RMS.  1e-4 leaves room for that and fails any wrong
state, position or route, which move the logits by percents.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.models import lm, mlp, moe, params as pm
from repro.models.reference import granite_h as ref
from repro.obs import Observability, Registry, Tracer
from repro.serve.lm_engine import Engine, Request

REL_TOL = 1e-4
ARCH = "granite-4.0-h-small"


def _f32_params(cfg, seed=0):
    specs = jax.tree.map(lambda s: dataclasses.replace(s, dtype=jnp.float32),
                         lm.model_specs(cfg), is_leaf=pm.is_spec)
    return pm.init(specs, jax.random.PRNGKey(seed))


def _rel_gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max(axis=-1) / np.sqrt((want * want).mean(axis=-1))


@pytest.fixture(scope="module")
def smoke():
    cfg = cb.smoke(ARCH)
    return cfg, _f32_params(cfg)


def _request(rng, cfg, n, new):
    return Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                   max_new_tokens=new, logits_at=(0, 1, new // 2, new - 1))


@pytest.mark.parametrize("schedule", ["staggered", "at_once"])
def test_engine_matches_reference(smoke, schedule):
    """Requests join at different steps and slots; with two slots the third
    request reuses the slot the short one left.  Every captured logit row
    matches the reference run teacher-forced on the engine's tokens."""
    cfg, params = smoke
    rng = np.random.default_rng(1)
    a, b, c = (_request(rng, cfg, 5, 7), _request(rng, cfg, 11, 3),
               _request(rng, cfg, 20, 5))
    eng = Engine(params, cfg, batch_size=2, max_len=64, min_bucket=8)
    if schedule == "staggered":
        eng.submit([a])
        eng.step()
        eng.step()
        eng.submit([b, c])
        eng.serve()
    else:
        eng.serve([a, b, c])
    st = eng.stats()
    assert st["prefill_calls"] == 3 and st["requests"] == 3
    with jax.default_matmul_precision("highest"):
        for r in (a, b, c):
            assert r.output.shape == (r.max_new_tokens,)
            toks = np.r_[r.prompt, r.output[:-1]]
            want = np.asarray(ref.forward(params, cfg, toks))
            pos = len(r.prompt) - 1 + np.asarray(r.logits_at)
            got = np.asarray(r.logits)[: len(r.logits_at)]
            assert _rel_gap(got, want[pos]).max() < REL_TOL
            # greedy: every output token is the argmax of the reference
            np.testing.assert_array_equal(
                r.output, np.argmax(want[len(r.prompt) - 1:], axis=-1))


@pytest.mark.parametrize("length", [3, 8, 13])
def test_padded_prefill_leaves_unpadded_state(smoke, length):
    """A prompt right-padded to a bucket of 16 leaves the same K/V rows
    (zero past the prompt), SSM state and conv state as the prompt alone.
    Equal up to float32 summation order: the chunked SSD splits 16 and
    ``length`` tokens into different chunks, which moves the later layers'
    inputs by about 1e-7."""
    cfg, params = smoke
    toks = np.random.default_rng(length).integers(0, cfg.vocab_size, length)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :length] = toks
    n = jnp.asarray([length], jnp.int32)
    with jax.default_matmul_precision("highest"):
        lg_p, c_p = lm.prefill_rows(params, cfg, jnp.asarray(padded), n)
        lg_u, c_u = lm.prefill_rows(params, cfg, jnp.asarray(toks[None], jnp.int32), n,
                                    capacity=16)
    assert _rel_gap(lg_p, lg_u).max() < 1e-6
    np.testing.assert_allclose(c_p.attn.k, c_u.attn.k, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c_p.attn.v, c_u.attn.v, rtol=1e-5, atol=1e-6)
    assert not np.asarray(c_p.attn.k)[:, :, length:].any()
    np.testing.assert_allclose(c_p.mamba.ssm, c_u.mamba.ssm, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(c_p.mamba.conv, c_u.mamba.conv, rtol=1e-5, atol=1e-6)
    assert int(c_p.lengths[0]) == length


@pytest.mark.parametrize("shares", [8, 2])
def test_expert_shares_add_up_to_the_uncut_layer(smoke, shares):
    """Disjoint expert shares, each routed over all experts, with the shared
    expert counted once, add up to the uncut layer and to the reference."""
    cfg, _ = smoke
    stack = _f32_params(cfg, seed=3)["mamba_layers"]["moe"]
    p = jax.tree.map(lambda a: a[0], stack)
    # the experts of one layer, as a stack of one layer
    experts = ("w_gate", "w_up", "w_down")
    one = dict(p, **{k: stack[k][:1] for k in experts})
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, cfg.d_model), jnp.float32)
    width = cfg.n_experts // shares
    with jax.default_matmul_precision("highest"):
        full = moe.held_moe_ffn(one, cfg, x, 0)
        parts = []
        for lo in range(0, cfg.n_experts, width):
            part = dict(one, **{k: one[k][:, lo:lo + width] for k in experts})
            parts.append(moe.held_moe_ffn(
                part, dataclasses.replace(cfg, n_experts_held=width, expert_offset=lo), x, 0))
        # each share adds the shared expert; count it once
        total = sum(parts) - (shares - 1) * mlp.mlp(p["shared"], x)
        want = ref.moe(p, x[0], cfg)
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(total[0], want, rtol=1e-5, atol=1e-6)


def _count_pulls(monkeypatch):
    """Count device-to-host fetches: every one goes through an array's
    ``_value`` the first time it is read."""
    from jax._src import array as jarray

    fget = jarray.ArrayImpl._value.fget
    seen = [0]

    def counted(self):
        if self._npy_value is None:
            seen[0] += 1
        return fget(self)

    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(counted))
    return seen


@pytest.mark.parametrize("arch", [ARCH, "llama3.2-1b"])
def test_one_host_pull_per_decode_step(monkeypatch, arch):
    cfg = cb.smoke(arch)
    params = pm.init(lm.model_specs(cfg), jax.random.PRNGKey(0))
    eng = Engine(params, cfg, batch_size=3, max_len=48, min_bucket=8)
    eng.warmup()
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=m) for n, m in ((4, 6), (9, 3), (6, 5), (12, 4))]
    pulls = _count_pulls(monkeypatch)
    eng.submit(reqs)
    steps = 0
    while eng.queue_depth():
        before = pulls[0]
        eng.step()
        steps += 1
        assert pulls[0] - before == 1
    st = eng.stats()
    assert st["decode_steps"] == st["pulls"] == steps
    assert st["decode_tokens"] + len(reqs) == sum(r.max_new_tokens for r in reqs)


def test_spans_and_counters_match_stats(smoke):
    cfg, params = smoke
    reg, tracer = Registry(), Tracer()
    eng = Engine(params, cfg, batch_size=2, max_len=48, min_bucket=8,
                 observability=Observability(tracer=tracer, metrics=reg))
    rng = np.random.default_rng(5)
    eng.serve([_request(rng, cfg, n, 4) for n in (6, 10, 3)])
    st = eng.stats()
    snap = reg.snapshot()
    assert snap["lm_decode_steps_total"]["value"] == st["decode_steps"]
    assert snap["lm_decode_tokens_total"]["value"] == st["decode_tokens"]
    assert snap["lm_prefill_tokens_total"]["value"] == st["prefill_tokens"] == 19
    assert snap["lm_request_queue_seconds"]["count"] == 3
    for phase in ("admit", "prefill", "decode", "drain", "retire"):
        key = f'lm_host_seconds_total{{phase="{phase}"}}'
        assert snap[key]["value"] == pytest.approx(st["host_s"][phase])
    names = {e["name"] for e in tracer.export()["traceEvents"] if e.get("ph") == "X"}
    assert names >= {"lm.admit", "lm.prefill", "lm.decode", "lm.drain", "lm.retire"}


def test_published_config_counts_32b_and_8p8b_active():
    cfg = cb.get(ARCH)
    specs = lm.model_specs(cfg)
    total = pm.param_count(specs)
    expert = sum(int(np.prod(specs[k]["moe"][w].shape))
                 for k in ("mamba_layers", "attn_layers") for w in ("w_gate", "w_up", "w_down"))
    active = total - expert + expert * cfg.top_k / cfg.n_experts
    assert total / 1e9 == pytest.approx(32.2, abs=0.05)
    assert active / 1e9 == pytest.approx(8.8, abs=0.05)
    assert cfg.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"] == [5, 15, 25, 35]


@pytest.mark.parametrize("seed", [6, 2**33 + 5])
def test_bench_reference_agrees_with_the_program_reference(smoke, seed):
    """``bench/lm_reference.py`` (which imports nothing of the program) on the
    benchmark's weights in the published layout (``bench/lm_weights.py``),
    and ``models/reference/granite_h.py`` on the same weights converted into
    the program's layout, give the same logits: the converter splits and
    transposes every published tensor where the program expects it."""
    cfg, _ = smoke
    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
    sys.path.insert(0, bench)
    try:
        import lm_reference
        import lm_weights
    finally:
        sys.path.remove(bench)
    config = {"hidden_size": cfg.d_model, "mamba_expand": cfg.ssm_expand,
              "mamba_d_head": cfg.ssm_head_dim, "mamba_d_state": cfg.ssm_state,
              "mamba_n_heads": cfg.ssm_heads, "mamba_d_conv": 4,
              "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
              "intermediate_size": cfg.d_ff, "shared_intermediate_size": cfg.shared_expert_ff,
              "num_experts_per_tok": cfg.top_k, "vocab_size": cfg.vocab_size,
              "published": {"num_local_experts": cfg.n_experts},
              "num_local_experts": cfg.experts_held, "expert_offset": cfg.expert_offset,
              "attention_multiplier": cfg.attention_multiplier,
              "embedding_multiplier": cfg.embedding_multiplier,
              "residual_multiplier": cfg.residual_multiplier,
              "logits_scaling": cfg.logits_scaling, "rms_norm_eps": cfg.norm_eps,
              "layer_types": list(cfg.layer_types), "num_hidden_layers": cfg.n_layers}
    specs = jax.tree.map(lambda s: dataclasses.replace(s, dtype=jnp.float32),
                         lm.model_specs(cfg), is_leaf=pm.is_spec)
    params = lm_weights.to_program(config, seed, pm.shape_structs(specs))
    toks = np.random.default_rng(6).integers(0, cfg.vocab_size, 17).astype(np.int32)
    [(a, _)] = lm_reference.run(
        lambda i: lm_weights.draw_layer(config, seed, i), lm_weights.draw_embed(config, seed),
        lm_weights.draw_final_norm(config, seed), lm_reference.sizes(config), [toks],
        [np.arange(toks.size)])[lm_reference.EXACT]
    b = np.asarray(ref.forward(params, cfg, toks))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
