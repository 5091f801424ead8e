"""Batched serving example: continuous-batching engine over the unified LM.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import jax
import numpy as np

from repro.configs import base as cb
from repro.models import lm, params as pm
from repro.serve.lm_engine import Engine, Request


def main():
    cfg = cb.smoke("llama3.2-1b")
    params = pm.init(lm.model_specs(cfg), jax.random.PRNGKey(0))
    eng = Engine(params, cfg, batch_size=4)
    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
                    max_new_tokens=12)
            for n in (5, 9, 7, 4, 11, 6)]
    out = eng.serve(reqs)
    for i, r in enumerate(out):
        print(f"req {i}: prompt len {len(r.prompt):2d} -> {r.output.tolist()}")


if __name__ == "__main__":
    main()
