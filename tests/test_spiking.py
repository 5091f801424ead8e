"""SpikingLinear (beyond-paper ESAM-mode LM layer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spiking
from repro.models import params as pm


def test_forward_matches_cim_kernel_plane():
    """The layer's forward MAC == the ESAM binary MAC (kernels plane)."""
    from repro.core import packing
    from repro.kernels.cim_popcount import ops as pop_ops

    key = jax.random.PRNGKey(0)
    params = pm.init(spiking.spiking_linear_specs(128, 128), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 128))
    out = spiking.spiking_linear(params, x)
    spikes = (x >= 0).astype(jnp.float32)
    bits = ((jnp.sign(params["w"]) + 1) // 2).astype(jnp.int8)
    vmem = pop_ops.cim_popcount_matmul(
        packing.pack_spikes(spikes), packing.pack_weight_planes(bits),
        use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out - params["b"]),
                               np.asarray(vmem, np.float32), atol=1e-4)


def test_top_p_arbiter_limits_events():
    x = jnp.asarray([[5.0, 3.0, -1.0, 4.0, 0.5]])
    masked = spiking.top_p_arbiter(x, 2)
    assert int((masked >= 0).sum()) == 2     # only the 2 largest remain active
    assert float(spiking.event_rate(x, ports=2)) == pytest.approx(0.4)


def test_gradients_flow_through_ste():
    params = pm.init(spiking.spiking_linear_specs(64, 32), jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 64)) * 0.1

    def loss(p, x):
        return jnp.sum(spiking.spiking_linear(p, x) ** 2)

    g = jax.grad(loss)(params, x)
    assert float(jnp.abs(g["w"]).sum()) > 0
    assert np.isfinite(float(jnp.abs(g["w"]).max()))


def test_trains_a_toy_task():
    """Binary layer learns a linearly separable task through the STE."""
    key = jax.random.PRNGKey(3)
    w_true = jax.random.normal(key, (32,))
    x = jax.random.normal(jax.random.fold_in(key, 1), (512, 32))
    y = (x @ w_true > 0).astype(jnp.int32)
    params = pm.init(spiking.spiking_linear_specs(32, 2), jax.random.fold_in(key, 2))

    def loss_fn(p):
        logits = spiking.spiking_linear(p, x)
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(lp, y[:, None], axis=1).mean()

    @jax.jit
    def step(p, lr=0.1):
        l, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), l

    for _ in range(150):
        params, l = step(params)
    logits = spiking.spiking_linear(params, x)
    acc = float((logits.argmax(-1) == y).mean())
    # {0,1} spikes discard the magnitude/sign detail of x, capping a single
    # binary layer well below 100% on this task; >0.65 shows the STE learns.
    assert acc > 0.65, acc
