"""Serving engine: batched greedy decode, continuous batching, eos handling."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cb
from repro.models import lm, params as pm
from repro.serve.lm_engine import Engine, Request


def _engine(arch="llama3.2-1b", batch_size=2):
    cfg = cb.smoke(arch)
    params = pm.init(lm.model_specs(cfg), jax.random.PRNGKey(0))
    return Engine(params, cfg, batch_size=batch_size), cfg


def test_engine_serves_batch():
    eng, cfg = _engine()
    reqs = [Request(prompt=np.arange(5) % cfg.vocab_size, max_new_tokens=4)
            for _ in range(2)]
    out = eng.serve(reqs)
    for r in out:
        assert r.output is not None and r.output.shape == (4,)
        assert (0 <= r.output).all() and (r.output < cfg.vocab_size).all()


def test_engine_queues_beyond_batch_size():
    eng, cfg = _engine(batch_size=2)
    reqs = [Request(prompt=np.asarray([1, 2, 3]), max_new_tokens=3) for _ in range(5)]
    out = eng.serve(reqs)
    assert all(r.output is not None for r in out)


def test_engine_greedy_matches_manual_decode():
    eng, cfg = _engine()
    prompt = np.asarray([5, 6, 7, 8])
    out = eng.serve([Request(prompt=prompt, max_new_tokens=3)])[0].output
    # manual greedy rollout with the raw model API
    params = eng.params
    toks = jnp.asarray(prompt)[None, :]
    logits, caches = lm.prefill(params, cfg, {"tokens": toks}, cache_len=4 + 3)
    manual = []
    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        manual.append(int(nxt[0, 0]))
        logits, caches = lm.decode_step(params, cfg, nxt, caches)
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    np.testing.assert_array_equal(out, np.asarray(manual))


def test_engine_eos_stops_early():
    eng, cfg = _engine()
    # find the first emitted token, then use it as eos for a second request
    probe = eng.serve([Request(prompt=np.asarray([1, 2]), max_new_tokens=1)])[0]
    eos = int(probe.output[0])
    r = eng.serve([Request(prompt=np.asarray([1, 2]), max_new_tokens=8, eos_id=eos)])[0]
    assert len(r.output) == 1 and int(r.output[0]) == eos
