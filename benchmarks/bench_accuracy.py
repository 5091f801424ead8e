"""Paper Sec 4.4.2 (V3): BNN training + lossless BNN->SNN conversion.
The conversion-exactness is the actual claim of [15]; absolute accuracy is on
the synthetic digit set (no MNIST offline — DESIGN.md §8)."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import emit, time_call
except ModuleNotFoundError:  # direct `python benchmarks/bench_accuracy.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import emit, time_call
from repro.core.esam import bnn, conversion, cost_model as cm
from repro.data import digits


def run():
    x, y = digits.make_spike_dataset(4096, seed=0)
    x_train, y_train = jnp.asarray(x[:3072]), jnp.asarray(y[:3072])
    x_test, y_test = jnp.asarray(x[3072:]), jnp.asarray(y[3072:])

    # warmup=0: a 250-step training run is too expensive to execute twice and
    # amortizes its own compile; everything cheaper uses the warmed default.
    us, (params, train_acc) = time_call(
        lambda: bnn.fit(jax.random.PRNGKey(0), cm.PAPER_TOPOLOGY,
                        x_train, y_train, steps=250, batch=128),
        repeats=1, warmup=0)
    net = conversion.bnn_to_snn(params)
    bnn_pred = bnn.forward(params, x_test).argmax(-1)
    snn_pred = net.plan(mode="functional")(x_test.astype(bool)).logits.argmax(-1)
    bnn_acc = float((bnn_pred == y_test).mean())
    snn_acc = float((snn_pred == y_test).mean())
    mismatch = int((bnn_pred != snn_pred).sum())
    emit("accuracy_bnn_to_snn", us,
         f"bnn_test_acc={bnn_acc*100:.2f};snn_test_acc={snn_acc*100:.2f};"
         f"pred_mismatches={mismatch}(conversion exact iff 0);"
         f"paper_mnist_acc=97.64")


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
