"""Fused WaveNet gate as a Pallas TPU kernel.

The gated activation ``tanh(a) * sigmoid(b)`` over the two halves of a
WaveNet pre-activation is the elementwise hot op inside every flow layer
(:func:`sonata_tpu.models.modules.wn`).  XLA fuses the plain-jnp version
well, so the Pallas kernel exists to pin the fusion (both transcendentals
and the multiply stay one VMEM pass regardless of surrounding graph shape)
and to serve as this codebase's template for hand kernels.

Design notes:
- The conditioning add (``x + g``) happens *outside* the kernel in jnp —
  XLA fuses it into the producing conv, and the kernel never sees a
  zeros tensor on the single-speaker path.
- The kernel takes the two halves as separate refs, so every block is
  lane-aligned regardless of the hidden size (192 in Piper voices is not
  a multiple of the 128-lane tile; slicing inside the kernel would hit an
  unaligned lane offset).
- Rows tile in blocks of 256 over the flattened ``[B*T, H]`` halves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_BLOCK_ROWS = 256


def _gate_kernel(a_ref, b_ref, out_ref):
    out_ref[:] = jnp.tanh(a_ref[:]) * jax.nn.sigmoid(b_ref[:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_gate_pallas(y, *, interpret: bool = False):
    """``y: [B, T, 2H]`` (pre-activation incl. conditioning) → ``[B, T, H]``
    computing ``tanh(y[..., :H]) * sigmoid(y[..., H:])``."""
    b, t, two_h = y.shape
    hidden = two_h // 2
    rows = b * t
    a = y[..., :hidden].reshape(rows, hidden)
    bb = y[..., hidden:].reshape(rows, hidden)
    pad = (-rows) % _BLOCK_ROWS
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        bb = jnp.pad(bb, ((0, pad), (0, 0)))
    n_blocks = a.shape[0] // _BLOCK_ROWS

    out = pl.pallas_call(
        _gate_kernel,
        # inside a shard_map the output varies over the same mesh axes
        # as the input (empty set anywhere else)
        out_shape=jax.ShapeDtypeStruct((a.shape[0], hidden), y.dtype,
                                       vma=jax.typeof(a).vma),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, hidden), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BLOCK_ROWS, hidden), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, hidden), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(a, bb)
    return out[:rows].reshape(b, t, hidden)


def fused_gate_reference(y):
    """jnp reference implementation (and the off-TPU fallback)."""
    hidden = y.shape[-1] // 2
    return jnp.tanh(y[..., :hidden]) * jax.nn.sigmoid(y[..., hidden:])


def fused_gate(x, g=None, mesh=None):
    """Gated activation with optional conditioning: ``x: [B, T, 2H]``,
    ``g: [B, 1, 2H]`` or None.  Pallas on TPU, jnp elsewhere.

    ``mesh``: the voice's device mesh when the caller is a data-sharded
    ``jax.jit`` (XLA cannot partition a Mosaic kernel by itself, so each
    chip runs the kernel on its own batch rows under a ``shard_map``);
    None on one device and inside an enclosing ``shard_map``."""
    y = x if g is None else x + g
    if jax.default_backend() != "tpu":
        return fused_gate_reference(y)
    if mesh is None:
        return fused_gate_pallas(y)
    from ..parallel.mesh import DATA_AXIS

    spec = P(DATA_AXIS)
    return jax.shard_map(fused_gate_pallas, mesh=mesh, in_specs=spec,
                         out_specs=spec)(y)
