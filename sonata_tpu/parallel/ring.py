"""Ring attention: exact attention over a sequence-sharded axis.

Long-context support is first-class in this framework (the reference bounds
every graph to one sentence and chunks inside it, SURVEY §5 "long-context";
we must also serve inputs that exceed one chip's memory).  The mechanism is
the standard ring schedule: each device holds a shard of the sequence; K/V
blocks rotate around the ring via ``lax.ppermute`` (XLA lowers this to ICI
neighbor exchanges) while each device accumulates its queries' attention
online (flash-attention style running max/denominator), so the result is
*exact* attention with O(T/n) memory per chip and compute/communication
overlap handled by XLA's async collectives.

Used via ``shard_map`` over the ``seq`` axis of the mesh
(:func:`ring_attention`), or directly inside an spmd region
(:func:`ring_attention_sharded`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .mesh import SEQ_AXIS


def _block_attend(q, k, v, mask, m_prev, l_prev, acc_prev, scale,
                  extra_v=None):
    """One K/V block of online-softmax attention.

    q: [B, H, Tq, D]; k/v: [B, H, Tk, D]; mask: [B, 1, Tq, Tk] additive
    (also carries any extra logits bias, e.g. relative-position terms).
    ``extra_v``: optional [Tq, Tk, D] per-pair value contribution (the
    relative-value table), accumulated with the same weights.
    Carries the flash-attention running statistics (m, l, acc).
    """
    logits = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k)
    if mask is not None:
        logits = logits + mask
    m_cur = jnp.max(logits, axis=-1)  # [B, H, Tq]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new[..., None])
    l_cur = jnp.sum(p, axis=-1)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + l_cur
    upd = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    if extra_v is not None:
        upd = upd + jnp.einsum("bhqk,qkd->bhqd", p, extra_v)
    acc_new = acc_prev * alpha[..., None] + upd
    return m_new, l_new, acc_new


def ring_attention_sharded(q, k, v, kv_valid, *, axis_name: str = SEQ_AXIS):
    """Exact attention where q/k/v are already sequence-sharded per device.

    Must run inside ``shard_map`` (or any spmd region) over ``axis_name``.

    q, k, v: [B, H, T_local, D] local shards.
    kv_valid: [B, T_local] float/bool — 1 for real positions (padding mask
    travels with its K/V shard around the ring).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    b, h, tq, d = q.shape

    # derive carries from q so they inherit q's varying-axis type under
    # shard_map (a plain jnp.zeros would be axis-invariant and fail the
    # fori_loop carry check on jax >= 0.8)
    m0 = jnp.full_like(q[..., 0], -jnp.inf)
    l0 = jnp.zeros_like(q[..., 0])
    acc0 = jnp.zeros_like(q)

    def step(i, carry):
        m, l, acc, k_blk, v_blk, valid_blk = carry
        mask = jnp.where(valid_blk[:, None, None, :] > 0, 0.0, -1e9)
        mask = mask.astype(q.dtype)
        m, l, acc = _block_attend(q, k_blk, v_blk, mask, m, l, acc, scale)
        # rotate K/V (and their validity) one step around the ring
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        valid_blk = lax.ppermute(valid_blk, axis_name, perm)
        return m, l, acc, k_blk, v_blk, valid_blk

    m, l, acc, _, _, _ = lax.fori_loop(
        0, n, step, (m0, l0, acc0, k, v, kv_valid.astype(q.dtype)))
    del idx  # ring is rotation-symmetric; no per-device offsets needed
    return acc / jnp.maximum(l[..., None], 1e-9)


def ring_rel_attention_sharded(q, k, v, kv_valid, rel_k, rel_v, *,
                               window: int, axis_name: str = SEQ_AXIS):
    """Ring attention with VITS's learned windowed relative-position
    embeddings (the text encoder's attention flavor,
    :func:`sonata_tpu.models.modules.rel_attention`).

    The relative term touches only positions with ``|s - t| <= window``
    (window=4 in Piper VITS), so on a ring it is nonzero only for the
    local block and its immediate neighbors — the gather below evaluates
    it per rotating block from each block's global offset.

    q, k, v: [B, H, T_local, D] local shards; kv_valid: [B, T_local];
    rel_k, rel_v: [2*window+1, D] (position ``r`` ⇔ offset ``r - window``).
    Must run inside ``shard_map`` over ``axis_name``.
    """
    n = lax.axis_size(axis_name)  # static: unrolled ring schedule
    idx = lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    b, h, t_loc, d = q.shape
    w = window

    # query·rel-key for all 2w+1 offsets, hoisted out of the ring loop
    qrel = jnp.einsum("bhtd,rd->bhtr", q * scale, rel_k)  # [B,H,T,2w+1]
    t_idx = jnp.arange(t_loc)

    m = jnp.full_like(q[..., 0], -jnp.inf)
    l = jnp.zeros_like(q[..., 0])
    acc = jnp.zeros_like(q)
    k_blk, v_blk = k, v
    valid_blk = kv_valid.astype(q.dtype)

    for i in range(n):
        src = (idx - i) % n  # which global block this k/v shard is
        off = (src - idx) * t_loc
        delta = off + (t_idx[None, :] - t_idx[:, None])  # [Tq, Tk] s - t
        in_win = (jnp.abs(delta) <= w)
        ridx = jnp.clip(delta + w, 0, 2 * w)  # [Tq, Tk]

        rel_term = jnp.take_along_axis(
            qrel, jnp.broadcast_to(ridx, (b, h, t_loc, t_loc)), axis=-1)
        bias = (jnp.where(in_win, rel_term, 0.0)
                + jnp.where(valid_blk[:, None, None, :] > 0,
                            0.0, -1e9)).astype(q.dtype)
        # relative-value table gathered per (t, s) pair (zero outside
        # the window)
        rel_v_g = jnp.where(in_win[..., None], rel_v[ridx], 0.0)
        m, l, acc = _block_attend(q, k_blk, v_blk, bias, m, l, acc, scale,
                                  extra_v=rel_v_g)
        if i < n - 1:  # final block needs no rotation
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
            valid_blk = lax.ppermute(valid_blk, axis_name, perm)

    return acc / jnp.maximum(l[..., None], 1e-9)


def halo_exchange(x, pad_left: int, pad_right: int, *,
                  axis_name: str = SEQ_AXIS):
    """Extend a sequence-sharded ``[B, T_local, C]`` block with its
    neighbors' boundary columns (zeros at the sequence ends, matching the
    zero padding a conv sees on an unsharded sequence).

    The permutes are non-circular: device 0's left halo and device n-1's
    right halo stay zero (``ppermute`` fills non-received slots with 0).
    """
    n = lax.axis_size(axis_name)
    parts = []
    if pad_left:
        left = lax.ppermute(x[:, -pad_left:], axis_name,
                            [(j, j + 1) for j in range(n - 1)])
        parts.append(left)
    parts.append(x)
    if pad_right:
        right = lax.ppermute(x[:, :pad_right], axis_name,
                             [(j + 1, j) for j in range(n - 1)])
        parts.append(right)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x


def ring_attention(q, k, v, lengths, mesh: Mesh, *,
                   axis_name: str = SEQ_AXIS):
    """Convenience wrapper: shard [B, H, T, D] q/k/v over the mesh's ``seq``
    axis and run :func:`ring_attention_sharded`.

    ``lengths``: [B] true sequence lengths (positions beyond are masked).
    T must be divisible by the size of the seq axis.
    """
    t = q.shape[2]
    positions = jnp.arange(t)[None, :]  # [1, T]
    kv_valid = (positions < lengths[:, None]).astype(q.dtype)  # [B, T]

    spec_qkv = P(None, None, axis_name, None)
    spec_valid = P(None, axis_name)

    fn = shard_map(
        partial(ring_attention_sharded, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_valid),
        out_specs=spec_qkv,
    )
    return fn(q, k, v, kv_valid)
