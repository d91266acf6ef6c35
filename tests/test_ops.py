"""The WaveNet gate (:func:`sonata_tpu.models.modules.gate`): the one
gated activation every platform runs."""

import jax
import jax.numpy as jnp
import numpy as np

from sonata_tpu.models.modules import gate


def _inputs(b=2, t=100, h=32, seed=0):
    r1, r2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(r1, (b, t, 2 * h))
    g = jax.random.normal(r2, (b, 1, 2 * h))
    return x, g


def test_gate_is_tanh_times_sigmoid_of_the_two_halves():
    x, g = _inputs(b=1, t=8, h=4)
    y = np.asarray(x + g, np.float64)
    want = np.tanh(y[..., :4]) / (1.0 + np.exp(-y[..., 4:]))
    np.testing.assert_allclose(np.asarray(gate(x + g)), want, atol=1e-6)
    # no conditioning: the pre-activation alone
    y = np.asarray(x, np.float64)
    want = np.tanh(y[..., :4]) / (1.0 + np.exp(-y[..., 4:]))
    np.testing.assert_allclose(np.asarray(gate(x)), want, atol=1e-6)


def test_gate_range_and_gradients():
    x, g = _inputs(b=1, t=16, h=8)
    out = gate(x + g)
    assert float(jnp.abs(out).max()) <= 1.0  # tanh*sigmoid bounded
    grads = jax.grad(lambda x: gate(x + g).sum())(x)
    assert bool(jnp.isfinite(grads).all())
