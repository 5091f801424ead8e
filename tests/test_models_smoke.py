"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward/train step on CPU, asserting output shapes + no NaNs, plus a
prefill+decode round for every arch with a decoder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.models import lm, params as pm

ARCHS = list(cb.ARCH_IDS)


def _batch(cfg, key, b=2, s=32):
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encdec:
        batch["src_frames"] = jax.random.normal(
            jax.random.fold_in(key, 1), (b, s, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = cb.smoke(arch)
    specs = lm.model_specs(cfg)
    params = pm.init(specs, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits = lm.forward_train(params, cfg, batch)
    assert logits.shape == (*batch["tokens"].shape, cfg.vocab_size)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    loss, grads = jax.value_and_grad(lm.loss_fn)(params, cfg, batch)
    assert np.isfinite(float(loss))
    gnorm = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch):
    cfg = cb.smoke(arch)
    specs = lm.model_specs(cfg)
    params = pm.init(specs, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(2))
    logits, caches = lm.prefill(params, cfg, batch)
    assert logits.shape == (2, cfg.vocab_size)
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    logits2, caches2 = lm.decode_step(params, cfg, tok, caches)
    assert logits2.shape == (2, cfg.vocab_size)
    assert not bool(jnp.isnan(logits2).any())


@pytest.mark.parametrize("arch", ["llama3.2-1b", "xlstm-125m", "zamba2-2.7b"])
def test_decode_matches_teacher_forcing(arch):
    """Prefill(t0..tn) + decode(t_{n+1}) must equal forward_train on the full
    sequence — the KV/state caches carry exactly the right context."""
    cfg = cb.smoke(arch)
    specs = lm.model_specs(cfg)
    params = pm.init(specs, jax.random.PRNGKey(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size)
    full_logits = lm.forward_train(params, cfg, {"tokens": tokens, "labels": tokens})
    # prefill on the first s-1 tokens, decode the final token
    pre_logits, caches = lm.prefill(
        params, cfg, {"tokens": tokens[:, : s - 1]}, cache_len=s)
    np.testing.assert_allclose(
        np.asarray(pre_logits, np.float32),
        np.asarray(full_logits[:, s - 2], np.float32), rtol=2e-2, atol=2e-2)
    dec_logits, _ = lm.decode_step(params, cfg, tokens[:, s - 1 :], caches)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(full_logits[:, s - 1], np.float32), rtol=2e-2, atol=2e-2)


def test_full_configs_match_assignment():
    """The full (non-smoke) configs carry the exact assigned hyperparameters."""
    expect = {
        "zamba2_2p7b": dict(n_layers=54, d_model=2560, n_heads=32, n_kv_heads=32,
                            d_ff=10240, vocab_size=32000, ssm_state=64),
        "seamless_m4t_medium": dict(d_model=1024, n_heads=16, n_kv_heads=16,
                                    d_ff=4096, vocab_size=256206),
        "stablelm_3b": dict(n_layers=32, d_model=2560, n_heads=32, n_kv_heads=32,
                            d_ff=6912, vocab_size=50304),
        "llama3p2_1b": dict(n_layers=16, d_model=2048, n_heads=32, n_kv_heads=8,
                            d_ff=8192, vocab_size=128256),
        "stablelm_1p6b": dict(n_layers=24, d_model=2048, n_heads=32, n_kv_heads=32,
                              d_ff=5632, vocab_size=100352),
        "granite_3_2b": dict(n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
                             d_ff=8192, vocab_size=49155),
        "xlstm_125m": dict(n_layers=12, d_model=768, n_heads=4, vocab_size=50304, d_ff=0),
        "chameleon_34b": dict(n_layers=48, d_model=8192, n_heads=64, n_kv_heads=8,
                              d_ff=22016, vocab_size=65536),
        "llama4_scout_17b_a16e": dict(n_layers=48, d_model=5120, n_heads=40,
                                      n_kv_heads=8, d_ff=8192, vocab_size=202048,
                                      n_experts=16, top_k=1),
        "kimi_k2_1t_a32b": dict(n_layers=61, d_model=7168, n_heads=64, n_kv_heads=8,
                                d_ff=2048, vocab_size=163840, n_experts=384, top_k=8),
    }
    for arch, fields in expect.items():
        cfg = cb.get(arch)
        for k, v in fields.items():
            assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)


def test_param_counts_in_expected_range():
    """Sanity: full-config param counts land near the advertised sizes."""
    expected_b = {
        "llama3p2_1b": (1.0, 1.7),
        "stablelm_1p6b": (1.3, 2.1),
        "granite_3_2b": (2.0, 3.0),
        "stablelm_3b": (2.5, 3.6),
        "zamba2_2p7b": (2.2, 3.6),
        "xlstm_125m": (0.1, 0.25),  # mLSTM up-proj 2x makes ours ~0.21B
        "chameleon_34b": (30.0, 38.0),
        "kimi_k2_1t_a32b": (950.0, 1150.0),
    }
    for arch, (lo, hi) in expected_b.items():
        cfg = cb.get(arch)
        n = pm.param_count(lm.model_specs(cfg)) / 1e9
        assert lo <= n <= hi, (arch, n)


def test_applicable_shapes_rules():
    """Skip rules: long_500k only for sub-quadratic; decode only with decoder."""
    assert "long_500k" in cb.applicable_shapes(cb.get("zamba2_2p7b"))
    assert "long_500k" in cb.applicable_shapes(cb.get("xlstm_125m"))
    for arch in ("llama3p2_1b", "chameleon_34b", "kimi_k2_1t_a32b",
                 "seamless_m4t_medium"):
        assert "long_500k" not in cb.applicable_shapes(cb.get(arch))
    assert "decode_32k" in cb.applicable_shapes(cb.get("seamless_m4t_medium"))
    total = sum(len(cb.applicable_shapes(cb.get(a))) for a in cb.ARCH_IDS)
    assert total == 35  # 30 base cells + 2 long_500k + 3 of granite-4.0-h-small
