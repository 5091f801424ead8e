"""The slot engine serves every LM family: its logits, with requests joining
at different steps, right-padded into buckets and sharing decode steps,
match a plain one-request rollout of the batch API (``lm.prefill`` of the
unpadded prompt, then ``lm.decode_step`` teacher-forced on the engine's own
tokens).  zamba2's long request outgrows its sliding window (64 at SMOKE)
by 36 positions, so the windowed slot path is compared with the ring-buffer
one.

Tolerance: both sides run in bfloat16 and sum in different orders (the
slot step scores the cached rows and the new token apart; padded against
unpadded rows; xLSTM's prompt runs through the recurrent step against the
chunked form): gaps of 2-5% of the logits' RMS at SMOKE widths.  A K/V row
written one position late, pads that advance the xLSTM state, or attention
past the window read 0.28 to 3.3 on the same requests; 0.1 lies between.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.models import lm, params as pm
from repro.serve.lm_engine import Engine, Request

REL_TOL = 0.1
#: one architecture of every family
ARCHS = ["llama3.2-1b", "chameleon-34b", "llama4-scout-17b-a16e", "zamba2-2.7b",
         "xlstm-125m", "seamless-m4t-medium", "granite-4.0-h-small"]


def _rollout(params, cfg, prompt, tokens):
    """Logits [len(tokens), V] of one unpadded sequence: the prompt's last
    position, then one decode step per token of ``tokens[:-1]``."""
    batch = {"tokens": jnp.asarray(prompt)[None]}
    if cfg.is_encdec:
        batch["src_frames"] = jnp.zeros((1, len(prompt), cfg.d_model), jnp.float32)
    prefill = jax.jit(lm.prefill, static_argnums=1, static_argnames="cache_len")
    decode = jax.jit(lm.decode_step, static_argnums=1)
    logits, caches = prefill(params, cfg, batch, cache_len=len(prompt) + len(tokens))
    out = [logits[0]]
    for t in tokens[:-1]:
        logits, caches = decode(params, cfg, jnp.asarray([[t]], jnp.int32), caches)
        out.append(logits[0])
    return np.asarray(jnp.stack(out), np.float64)


@pytest.mark.parametrize("arch", ARCHS)
def test_slot_engine_matches_one_request_rollout(arch):
    cfg = cb.smoke(arch)
    params = pm.init(lm.model_specs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=m, logits_at=(0, 1, m // 2, m - 1))
            for n, m in ((5, 7), (11, 3), (40, 60))]
    eng = Engine(params, cfg, batch_size=2, max_len=104, min_bucket=16)
    eng.submit(reqs[:1])
    eng.step()
    eng.step()
    eng.serve(reqs[1:])
    assert eng.stats()["requests"] == 3
    for r in reqs:
        assert r.output.shape == (r.max_new_tokens,)
        want = _rollout(params, cfg, r.prompt, r.output)[np.asarray(r.logits_at)]
        got = np.asarray(r.logits, np.float64)[: len(r.logits_at)]
        gap = np.abs(got - want).max(-1) / np.sqrt((want * want).mean(-1))
        assert gap.max() < REL_TOL, (arch, gap)
