"""Frame-domain sequence parallelism: coupling flow + HiFi-GAN across chips.

Long-context is first-class: the text encoder already rides the mesh's
``seq`` axis via ring attention, and this module extends the same axis
through the *frame* domain — the residual-coupling flow and the HiFi-GAN
decoder — so one long utterance's latent ``z`` (and its waveform) can
exceed a single chip's memory, sharded over frames.

Every frame-domain op has a bounded receptive field, so the schedule is
pure halo exchange (``parallel.ring.halo_exchange`` — neighbor ``ppermute``
over ICI, zeros at the true sequence ends, matching the zero padding an
unsharded conv sees):

- WaveNet convs (kernel 5, dilation 1): halo 2.
- HiFi-GAN resblock dilated convs (kernel ≤ 11, dilation ≤ 5): halo ≤ 25
  *samples at that stage's rate* per conv.
- Transposed upsampling convs (stride r, kernel k, pad (k−r)/2): extend
  the input by ``h = ceil((k−1−pad)/r)`` frames per side, run the same
  lhs-dilated conv, trim ``h·r`` output samples per side — exactly the
  global result, locally.
- Time-folded stages (under 128 channels, ``vits.decode_fold``): every
  convolution there, the transposed one included, is a plain ``SAME``
  convolution over folded steps and takes the first rule; its halo is a
  whole number of folded steps.

Numerics match the unsharded :func:`vits.flow_reverse` / :func:`vits.decode`
(tested in ``tests/test_parallel.py``).  The reference has no counterpart:
its decoder is a single-process ONNX session (``piper/src/lib.rs:342-399``).
"""

from __future__ import annotations

import math

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, SEQ_AXIS
from ..parallel.ring import halo_exchange
from . import modules as m
from .config import VitsHyperParams

Params = dict


def _conv_halo(x, p, *, dilation: int = 1):
    """SAME-padding conv over a sequence-sharded axis via halo exchange."""
    k = p["w"].shape[0]
    k_eff = (k - 1) * dilation + 1
    pl, pr = k_eff // 2, k_eff - 1 - k_eff // 2
    if pl == 0 and pr == 0:  # kernel-1: pointwise, no halo
        return m.conv1d(x, p)
    ext = halo_exchange(x, pl, pr)
    return m.conv1d(ext, p, dilation=dilation, padding=0)


def _tconv_halo(x, p, *, stride: int, padding: int):
    """Transposed conv over a sharded frame axis.

    Extends the input by ``h`` frames per side, applies the identical
    lhs-dilated conv, and trims ``h*stride`` output samples per side —
    the local segment of the global transposed conv.
    """
    k = p["w"].shape[0]
    a = k - 1 - padding
    h = max(math.ceil(a / stride), 0)
    ext = halo_exchange(x, h, h)
    y = m.conv_transpose1d(ext, p, stride=stride, padding=padding)
    trim = h * stride
    return y[:, trim: y.shape[1] - trim] if trim else y


def min_local_frames(hp: VitsHyperParams) -> int:
    """Smallest per-shard frame count for which every halo fits inside the
    immediate neighbor's shard at its stage's sample rate.

    ``halo_exchange`` is neighbor-only, so each stage needs
    ``local_len >= halo``; sample-rate halos (resblock dilated convs,
    transposed-conv extensions) divide back by the cumulative upsample
    product to frame units.  A time-folded stage (``vits.decode_fold``)
    exchanges whole folded steps, so its halos round up to its fold.
    """
    def halo(reach: int, fold: int) -> int:
        return fold * math.ceil(reach / fold) + 1

    need = (7 - 1) // 2 + 1  # conv_pre kernel 7 at frame rate
    need = max(need, (hp.flow_kernel_size - 1) // 2 + 1)  # WN convs
    prod = fold = 1
    for i, (r, k) in enumerate(zip(hp.upsample_rates,
                                   hp.upsample_kernel_sizes)):
        pad = (k - r) // 2
        h = max(math.ceil((k - 1 - pad) / r), 0)
        need = max(need, math.ceil(halo(h, fold) / prod))  # tconv input
        prod *= r
        # the fold the stage's channels allow, whatever its length: the
        # larger halo
        fold = m.fold_factor(hp.upsample_initial_channel // 2 ** (i + 1),
                             m.LANES)
        res_halo = max(halo((k_ * d - d) // 2, fold)
                       for k_, dils in zip(hp.resblock_kernel_sizes,
                                           hp.resblock_dilation_sizes)
                       for d in dils)
        need = max(need, math.ceil(res_halo / prod))
    return max(need, math.ceil(halo((7 - 1) // 2, fold) / prod))  # conv_post


def _flow_reverse_local(pf: Params, hp: VitsHyperParams, z, mask, g):
    from . import vits

    return vits.flow_reverse(pf, hp, z, mask, g=g, conv=_conv_halo)


def _decode_local_impl(p: Params, hp: VitsHyperParams, z, g,
                       compute_dtype=None):
    from . import vits

    return vits.decode_with(p, hp, z, g=g, conv=_conv_halo,
                            tconv=_tconv_halo, compute_dtype=compute_dtype)


def flow_reverse_sp(pf: Params, hp: VitsHyperParams, z, mask, mesh, g=None):
    """Sequence-parallel :func:`vits.flow_reverse`: ``z`` [B, F, C] sharded
    over the mesh's seq axis along frames."""
    spec = P(DATA_AXIS, SEQ_AXIS, None)
    g_spec = P(DATA_AXIS, None, None)
    if g is None:
        fn = shard_map(
            lambda zz, mm, pp: _flow_reverse_local(pp, hp, zz, mm, None),
            mesh=mesh, in_specs=(spec, spec, P()), out_specs=spec)
        return fn(z, mask, pf)
    fn = shard_map(
        lambda zz, mm, gg, pp: _flow_reverse_local(pp, hp, zz, mm, gg),
        mesh=mesh, in_specs=(spec, spec, g_spec, P()), out_specs=spec)
    return fn(z, mask, g, pf)


def decode_sp(p: Params, hp: VitsHyperParams, z, mesh, g=None,
              compute_dtype=None):
    """Sequence-parallel :func:`vits.decode`: frames sharded over the seq
    axis; returns the waveform [B, F*hop] with samples sharded the same
    way.  ``compute_dtype`` follows the same reduced-precision policy as
    the unsharded path (halo exchanges ride the narrower dtype too)."""
    spec_z = P(DATA_AXIS, SEQ_AXIS, None)
    spec_out = P(DATA_AXIS, SEQ_AXIS)
    g_spec = P(DATA_AXIS, None, None)
    pd = {"dec": p["dec"]}  # decode only touches the generator subtree
    if g is None:
        fn = shard_map(
            lambda zz, pp: _decode_local_impl(pp, hp, zz, None,
                                              compute_dtype=compute_dtype),
            mesh=mesh, in_specs=(spec_z, P()), out_specs=spec_out)
        return fn(z, pd)
    fn = shard_map(
        lambda zz, gg, pp: _decode_local_impl(pp, hp, zz, gg,
                                              compute_dtype=compute_dtype),
        mesh=mesh, in_specs=(spec_z, g_spec, P()), out_specs=spec_out)
    return fn(z, g, pd)
