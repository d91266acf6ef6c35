"""HiFi-GAN stages under 128 channels run with time folded into the channel
axis (``modules.fold_conv`` / ``fold_conv_transpose``, ``vits.decode_fold``):
an identity, held here against the plain convolutions it replaces.

Every comparison runs in float32 at ``highest`` and allows for one thing
only: a folded convolution sums the same products in another order (by
folded tap and input phase instead of by tap), so results differ by a few
float32 roundings of an O(1) sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sonata_tpu.models import modules as m
from sonata_tpu.models import vits
from sonata_tpu.models.config import QUALITY_PRESETS, VitsHyperParams


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def conv_params(seed: int, k: int, c_in: int, c_out: int) -> dict:
    return m._conv_init(jax.random.PRNGKey(seed), k, c_in, c_out)


def signal(seed: int, shape) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("dilation", (1, 3, 5))
@pytest.mark.parametrize("kernel", (3, 7, 11))
@pytest.mark.parametrize("channels", (16, 32, 64))
def test_folded_conv_equals_the_plain_one(channels, kernel, dilation):
    r = m.fold_factor(channels, 96)
    assert r == 128 // channels
    p = conv_params(kernel * dilation, kernel, channels, channels)
    x = signal(channels, (2, 96, channels))
    folded = m.fold_conv(p, r, dilation=dilation)
    reach = -(-(dilation * (kernel - 1) // 2) // r)
    assert folded["w"].shape == (2 * reach + 1, 128, 128)
    got = m.fold_time(m.conv1d(m.fold_time(x, 1, r), folded), r, 1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(m.conv1d(x, p, dilation=dilation)),
                               atol=1e-5)


def test_a_narrowing_conv_folds_too():
    """``conv_post``: 32 -> 1 channels, ``[B, T/4, 128] -> [B, T/4, 4]``,
    whose row-major order is the waveform's."""
    p = conv_params(0, 7, 32, 1)
    x = signal(1, (2, 64, 32))
    got = m.conv1d(m.fold_time(x, 1, 4), m.fold_conv(p, 4))
    assert got.shape == (2, 16, 4)
    np.testing.assert_allclose(np.asarray(got.reshape(2, 64)),
                               np.asarray(m.conv1d(x, p)[..., 0]), atol=1e-5)


@pytest.mark.parametrize("c_in,fold_in", ((128, 1), (64, 2), (32, 4)),
                         ids=("unfolded-input", "input-folded-by-2",
                              "input-folded-by-4"))
@pytest.mark.parametrize("stride,kernel", ((2, 4), (8, 16)))
def test_folded_conv_transpose_equals_the_plain_one(stride, kernel, c_in,
                                                    fold_in):
    padding = (kernel - stride) // 2
    p = conv_params(stride, kernel, c_in, c_in // 2)
    x = signal(c_in, (2, 24, c_in))
    folded = m.fold_conv_transpose(p, fold_in, stride=stride,
                                   padding=padding)
    assert folded["w"].shape[1:] == (fold_in * c_in,
                                     fold_in * stride * c_in // 2)
    got = m.conv1d(m.fold_time(x, 1, fold_in), folded)
    np.testing.assert_allclose(
        np.asarray(m.fold_time(got, fold_in * stride, 1)),
        np.asarray(m.conv_transpose1d(x, p, stride=stride, padding=padding)),
        atol=1e-5)


def test_fold_factor_reads_shapes_only():
    assert [m.fold_factor(c, 1024) for c in (512, 256, 128, 64, 32, 16)] \
        == [1, 1, 1, 2, 4, 8]
    assert m.fold_factor(96, 1024) == 1      # 128 is no multiple of it
    assert m.fold_factor(32, 1022) == 1      # nor the length of 4
    assert m.fold_factor(64, 1022) == 2


def plain_decode(pd: dict, hp: VitsHyperParams, z):
    """The generator composed from the plain primitives alone."""
    x = m.conv1d(z, pd["conv_pre"])
    n = len(hp.resblock_kernel_sizes)
    for i, (rate, k) in enumerate(zip(hp.upsample_rates,
                                      hp.upsample_kernel_sizes)):
        x = m.conv_transpose1d(jax.nn.leaky_relu(x, m.LRELU_SLOPE),
                               pd["ups"][i], stride=rate,
                               padding=(k - rate) // 2)
        total = 0.0
        for j in range(n):
            block, y = pd["resblocks"][i * n + j], x
            for c1, c2, d in zip(block["convs1"], block["convs2"],
                                 hp.resblock_dilation_sizes[j]):
                t = m.conv1d(jax.nn.leaky_relu(y, m.LRELU_SLOPE), c1,
                             dilation=d)
                y = y + m.conv1d(jax.nn.leaky_relu(t, m.LRELU_SLOPE), c2)
            total = total + y
        x = total / n
    x = m.conv1d(jax.nn.leaky_relu(x, m.LRELU_SLOPE), pd["conv_post"])
    return jnp.tanh(x)[..., 0]


GEOMETRIES = {
    # published channel counts, three frames: every narrow stage folds
    "high": (VitsHyperParams(**QUALITY_PRESETS["high"]), [1, 1, 2, 4]),
    # its second stage goes through the reshape from the sub-pixel form's
    # fold of 8 to its own of 2
    "x_low": (VitsHyperParams(**QUALITY_PRESETS["x_low"]), [1, 2, 4, 8]),
    # a folded stage before one whose transposed convolution has no folded
    # form (K - stride odd): unfolded in between
    "folded-then-plain": (VitsHyperParams(
        inter_channels=8, upsample_initial_channel=128,
        upsample_rates=(2, 2), upsample_kernel_sizes=(4, 5),
        resblock_kernel_sizes=(3,), resblock_dilation_sizes=((1, 3),)),
        [2, 1]),
}


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_decode_with_equals_the_plain_generator(geometry):
    hp, folds = GEOMETRIES[geometry]
    pd = vits.init_generator(jax.random.PRNGKey(3), hp, 0)
    z = signal(4, (2, 3, hp.inter_channels))
    assert vits.decode_fold(pd, hp, 3) == folds
    want = plain_decode(pd, hp, z)
    got = vits.decode_with({"dec": pd}, hp, z)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_decode_fold_follows_the_frames_and_the_geometry():
    hp = VitsHyperParams()
    pd = jax.eval_shape(lambda: vits.init_generator(jax.random.PRNGKey(0),
                                                    hp, 0))
    for frames in (768, 1024, 1536, 7):
        assert vits.decode_fold(pd, hp, frames) == [1, 1, 2, 4]
    # a transposed convolution outside the sub-pixel geometry has no
    # folded form: its stage stays as it was
    odd = VitsHyperParams(upsample_kernel_sizes=(16, 16, 4, 5))
    assert vits.decode_fold(pd, odd, 768) == [1, 1, 2, 1]
