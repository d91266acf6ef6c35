"""Neural building blocks for VITS, as pure JAX functions over param pytrees.

Design notes (TPU-first, not a port):

- The reference never contains this math — it executes a black-box ONNX graph
  via onnxruntime (``crates/sonata/models/piper/src/lib.rs:342-399``).  These
  modules re-implement the *architecture* of Piper-flavor VITS (text encoder
  with windowed relative attention, stochastic duration predictor over
  rational-quadratic-spline flows, residual-coupling flow with WaveNet
  blocks, HiFi-GAN decoder) natively in JAX so XLA owns fusion/layout.
- Everything is ``[batch, time, channels]`` (NTC): the lane dimension maps to
  channels, convs lower to MXU matmuls, and no transposes are needed between
  attention and conv blocks.  That holds down to :data:`LANES` (128)
  channels.  Below, XLA still puts channels on the minor axis and pads it to
  the tile: ``f32[8,196608,32]{2,0,1:T(8,128)}`` moves four times its bytes
  through HBM and fills a quarter of a 128 x 128 weight tile.  So the
  HiFi-GAN stages under 128 channels run *time-folded*: ``r = 128 // C``
  consecutive time steps ride the channel axis, ``[B, T / r, r * C]``, and
  each convolution's weights are folded to match (:func:`fold_conv`,
  :func:`fold_conv_transpose`; the rule is :func:`fold_factor`).  It is the
  same sum of the same products; only the float32 summation order differs.
- Params are plain nested dicts (a JAX pytree).  Each block has
  ``init_*(rng, ...) -> params`` and a pure ``apply`` function, so the whole
  model jits/pjits and weights import cleanly from Piper torch checkpoints.
- Masks are explicit ``[B, T, 1]`` float tensors; all shapes static — no
  data-dependent control flow anywhere (XLA traces once per bucket).
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

Params = dict

LRELU_SLOPE = 0.1


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _normal(rng, shape, std=0.02):
    return jax.random.normal(rng, shape, dtype=jnp.float32) * std


def _conv_init(rng, k, c_in, c_out):
    # kaiming-uniform-ish fan-in scaling, matching torch conv defaults
    bound = 1.0 / math.sqrt(c_in * k)
    w_rng, b_rng = jax.random.split(rng)
    return {
        "w": jax.random.uniform(w_rng, (k, c_in, c_out), jnp.float32, -bound, bound),
        "b": jax.random.uniform(b_rng, (c_out,), jnp.float32, -bound, bound),
    }


# ---------------------------------------------------------------------------
# conv primitives (NTC layout)
# ---------------------------------------------------------------------------

def conv1d(x, p, *, dilation: int = 1, stride: int = 1,
           padding: str | int = "SAME"):
    """1-D convolution, ``x: [B, T, C_in]``, weight ``[K, C_in, C_out]``."""
    if isinstance(padding, int):
        pad = [(padding, padding)]
    elif padding == "SAME":
        k_eff = (p["w"].shape[0] - 1) * dilation + 1
        pad = [(k_eff // 2, k_eff - 1 - k_eff // 2)]
    else:
        pad = padding
    y = lax.conv_general_dilated(
        x, p["w"], window_strides=(stride,), padding=pad,
        rhs_dilation=(dilation,),
        dimension_numbers=("NHC", "HIO", "NHC"),
    )
    return y + p["b"]


def conv_transpose1d(x, p, *, stride: int, padding: int):
    """Transposed 1-D conv matching torch ``ConvTranspose1d`` semantics.

    ``x: [B, T, C_in]``, weight stored ``[K, C_in, C_out]``.  Output length is
    ``(T-1)*stride - 2*padding + K`` — identical to torch, so HiFi-GAN
    upsample stacks produce exactly ``T * prod(rates)`` samples when
    ``padding=(K-stride)//2`` with even ``K-stride``.

    When the HiFi-GAN geometry holds (``K - stride == 2*padding``) this
    lowers to the sub-pixel form (:func:`conv_transpose1d_subpixel`): the
    textbook ``lhs_dilation`` lowering makes the MXU multiply mostly
    zeros — ``stride-1`` of every ``stride`` dilated input positions are
    stuffing — an ~8x FLOP waste at Piper's first upsample stage.
    (A time-folded decoder stage takes the sub-pixel kernel directly,
    :func:`fold_conv_transpose`, and does not come through here.)
    """
    k = p["w"].shape[0]
    if (k - stride == 2 * padding and stride > 1
            and os.environ.get("SONATA_TCONV", "subpixel") != "naive"):
        return conv_transpose1d_subpixel(x, p, stride=stride, padding=padding)
    y = lax.conv_general_dilated(
        x, jnp.flip(p["w"], 0), window_strides=(1,),
        padding=[(k - 1 - padding, k - 1 - padding)],
        lhs_dilation=(stride,),
        dimension_numbers=("NHC", "HIO", "NHC"),
    )
    return y + p["b"]


def conv_transpose1d_subpixel(x, p, *, stride: int, padding: int):
    """Transposed conv as a dense conv + depth-to-space (exact).

    Writing output index ``n = stride*b + r``, the transposed conv is, per
    phase ``r``, a small dense conv over the *un-dilated* input:

        y[s*b + r] = sum_d x[b + d] * w_flip[s*d + (K-1-pad-r)]

    so all ``stride`` phases stack into one conv with ``stride * C_out``
    output channels followed by a reshape — every MAC works on real data.
    Requires the exact-upsample geometry ``(T-1)s - 2p + K == T*s``, i.e.
    ``K - s == 2p`` (all Piper/HiFi-GAN stages satisfy this).
    """
    wsub, d_lo, d_hi = _subpixel_kernel(p["w"], stride, padding)
    y = lax.conv_general_dilated(
        x, wsub, window_strides=(1,), padding=[(-d_lo, d_hi)],
        dimension_numbers=("NHC", "HIO", "NHC"),
    )  # [B, T, s*C_out]
    return fold_time(y, stride, 1) + p["b"]


def _subpixel_kernel(w, stride: int, padding: int):
    """The dense kernel of :func:`conv_transpose1d_subpixel`:
    ``[taps, C_in, stride * C_out]`` (phase-major output channels), with
    the first and last tap's offset in input steps."""
    k, c_in, c_out = w.shape  # [K, C_in, C_out]
    s = stride
    wf = jnp.flip(w, 0)
    # tap range over d for any phase r: j = s*d + (k-1-padding-r) in [0, k)
    cs = [k - 1 - padding - r for r in range(s)]
    d_lo = min(math.ceil(-c / s) for c in cs)
    d_hi = max(math.floor((k - 1 - c) / s) for c in cs)
    taps = d_hi - d_lo + 1
    # gather kernel: [taps, C_in, s, C_out], zero where j falls outside
    wsub = jnp.zeros((taps, c_in, s, c_out), w.dtype)
    for r in range(s):
        c = cs[r]
        for d in range(d_lo, d_hi + 1):
            j = s * d + c
            if 0 <= j < k:
                wsub = wsub.at[d - d_lo, :, r, :].set(wf[j])
    return wsub.reshape(taps, c_in, s * c_out), d_lo, d_hi


# ---------------------------------------------------------------------------
# time folded into the channel axis (narrow HiFi-GAN stages)
# ---------------------------------------------------------------------------

#: the minor-axis tile of a TPU: vector lanes, the HBM tile's width and the
#: edge of the MXU's weight tile
LANES = 128


def fold_factor(channels: int, length: int) -> int:
    """How many consecutive time steps a ``[B, length, channels]``
    activation carries in its channel axis: enough to fill the lanes, if
    the shapes divide; 1 (unfolded) otherwise and from 128 channels up."""
    if channels >= LANES or LANES % channels:
        return 1
    r = LANES // channels
    return r if length % r == 0 else 1


def fold_time(x, r_from: int, r_to: int):
    """``[B, T / r_from, r_from * C]`` -> ``[B, T / r_to, r_to * C]``: the
    same values in the same row-major order.  Free in the graph, a
    physical relayout on a tiled device: a stage folds once and unfolds
    once, never in between."""
    if r_from == r_to:
        return x
    b, n, c = x.shape
    return x.reshape(b, n * r_from // r_to, c * r_to // r_from)


def fold_conv(p: Params, r: int, *, dilation: int = 1,
              first: Optional[int] = None) -> Params:
    """The convolution ``p`` (``SAME``, dilation ``dilation``) on
    ``[B, T, C]``, as the parameters of a plain ``SAME`` convolution on
    the time-folded ``[B, T / r, r * C]``.

    Tap ``j`` of ``p`` reads input step ``t + first + dilation * j``
    (``first`` defaults to ``SAME``'s).  Output phase ``p_`` of folded step
    ``m`` is time ``r * m + p_``; what it reads at offset ``o`` is phase
    ``pi`` of folded step ``m + q`` with ``r * q + pi = p_ + o``.  So with
    the kernel laid out densely over offsets, ``wd[o]``, the folded weight
    at (tap ``q``, input phase ``pi``, output phase ``p_``) is
    ``wd[r * q + pi - p_]``: a Toeplitz matrix in ``(r * q + pi, p_)``,
    built below by the skew that a tile-and-reshape gives (no gather, no
    per-tap update; 39 convolutions are folded per lessac-high program).
    The folded taps are symmetric, ``2 * reach + 1``, so ``SAME`` holds and
    the zeros beyond the ends of the sequence are the same zeros.
    """
    w = p["w"]
    k, c_in, c_out = w.shape
    if first is None:
        first = -(((k - 1) * dilation + 1) // 2)
    last = first + dilation * (k - 1)
    reach = max(math.ceil(-first / r), math.ceil(last / r), 0)
    taps = 2 * reach + 1
    # wd over offsets [-r*reach, r*reach], then r more zeros: the skew
    # wraps negative differences pi - p_ into them
    wd = lax.pad(w, jnp.zeros((), w.dtype),
                 [(r * reach + first, r * reach - last + r, dilation - 1),
                  (0, 0, 0), (0, 0, 0)])
    # row p_ of the reshape starts r*taps*p_ into a sequence of period
    # r*taps + 1, so its column u holds wd[u - p_]
    skew = jnp.tile(wd, (r, 1, 1))[: r * r * taps]
    wf = skew.reshape(r, taps, r, c_in, c_out)       # [p_, q, pi, ci, co]
    wf = wf.transpose(1, 2, 3, 0, 4).reshape(taps, r * c_in, r * c_out)
    return {"w": wf, "b": jnp.tile(p["b"], r)}


def fold_conv_transpose(p: Params, r: int, *, stride: int,
                        padding: int) -> Params:
    """The transposed convolution ``p`` from an input folded by ``r`` (1:
    unfolded), as the parameters of a plain ``SAME`` convolution whose
    output is folded by ``r * stride``.  The sub-pixel form *is* that
    convolution at ``r = 1`` (its ``[B, T, stride * C_out]`` is the output
    folded by ``stride``); a folded input folds its kernel like any
    other.  Needs the sub-pixel geometry, ``K - stride == 2 * padding``."""
    wsub, d_lo, _ = _subpixel_kernel(p["w"], stride, padding)
    return fold_conv({"w": wsub, "b": jnp.tile(p["b"], stride)}, r,
                     first=d_lo)


def layer_norm(x, p, eps: float = 1e-5):
    """LayerNorm over channels (last dim)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def init_layer_norm(c):
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}


# ---------------------------------------------------------------------------
# windowed relative-position multi-head attention (VITS text encoder)
# ---------------------------------------------------------------------------

def init_rel_attention(rng, channels: int, n_heads: int, window: int):
    head = channels // n_heads
    rngs = jax.random.split(rng, 6)
    std = (head ** -0.5)
    return {
        "q": _conv_init(rngs[0], 1, channels, channels),
        "k": _conv_init(rngs[1], 1, channels, channels),
        "v": _conv_init(rngs[2], 1, channels, channels),
        "o": _conv_init(rngs[3], 1, channels, channels),
        # learned relative embeddings over [-window, window]
        "emb_rel_k": _normal(rngs[4], (1, 2 * window + 1, head), std),
        "emb_rel_v": _normal(rngs[5], (1, 2 * window + 1, head), std),
    }


def _rel_to_abs(x):
    """[B*H, T, 2T-1] relative-indexed logits → [B*H, T, T] absolute."""
    b, t, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 1)))
    x = x.reshape(b, t * 2 * t)
    x = jnp.pad(x, ((0, 0), (0, t - 1)))
    x = x.reshape(b, t + 1, 2 * t - 1)
    return x[:, :t, t - 1:]


def _abs_to_rel(x):
    """[B*H, T, T] absolute attention weights → [B*H, T, 2T-1] relative."""
    b, t, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, t - 1)))
    x = x.reshape(b, t * (2 * t - 1))
    x = jnp.pad(x, ((0, 0), (t, 0)))
    x = x.reshape(b, t, 2 * t)
    return x[:, :, 1:]


def _rel_embeddings(emb, window, t):
    """Slice/pad the learned [-window, window] table to [2T-1] positions."""
    pad = max(t - window - 1, 0)
    start = max(window + 1 - t, 0)
    emb = jnp.pad(emb, ((0, 0), (pad, pad), (0, 0)))
    return lax.dynamic_slice_in_dim(emb, start, 2 * t - 1, axis=1)


def rel_attention(x, mask, p, *, n_heads: int, window: int):
    """Self-attention with learned relative position embeddings, window
    ±``window`` (VITS text encoder uses window=4).

    ``x: [B, T, C]``, ``mask: [B, T, 1]`` (1 = valid).
    """
    b, t, c = x.shape
    head = c // n_heads
    q = conv1d(x, p["q"])
    k = conv1d(x, p["k"])
    v = conv1d(x, p["v"])

    def split(u):  # [B, T, C] -> [B*H, T, head]
        return u.reshape(b, t, n_heads, head).transpose(0, 2, 1, 3).reshape(
            b * n_heads, t, head
        )

    q, k, v = split(q), split(k), split(v)
    scale = head ** -0.5
    logits = jnp.einsum("btd,bsd->bts", q * scale, k)
    # relative key contribution
    rel_k = _rel_embeddings(p["emb_rel_k"], window, t)  # [1, 2T-1, head]
    rel_logits = jnp.einsum("btd,msd->bts", q * scale, rel_k)
    logits = logits + _rel_to_abs(rel_logits)

    attn_mask = (mask[:, None, :, 0] * mask[:, :, None, 0])  # [B, T, T]
    attn_mask = jnp.repeat(attn_mask, n_heads, axis=0).reshape(b * n_heads, t, t)
    logits = jnp.where(attn_mask > 0, logits, -1e4)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bts,bsd->btd", weights, v)
    # relative value contribution
    rel_v = _rel_embeddings(p["emb_rel_v"], window, t)  # [1, 2T-1, head]
    out = out + jnp.einsum("btm,bmd->btd", _abs_to_rel(weights), rel_v)

    out = out.reshape(b, n_heads, t, head).transpose(0, 2, 1, 3).reshape(b, t, c)
    return conv1d(out, p["o"]) * mask


# ---------------------------------------------------------------------------
# conv feed-forward (VITS encoder FFN)
# ---------------------------------------------------------------------------

def init_ffn(rng, channels, filter_channels, kernel):
    r1, r2 = jax.random.split(rng)
    return {
        "c1": _conv_init(r1, kernel, channels, filter_channels),
        "c2": _conv_init(r2, kernel, filter_channels, channels),
    }


def ffn(x, mask, p):
    y = conv1d(x * mask, p["c1"])
    y = jax.nn.relu(y)
    return conv1d(y * mask, p["c2"]) * mask


# ---------------------------------------------------------------------------
# transformer encoder stack
# ---------------------------------------------------------------------------

def init_transformer(rng, *, channels, filter_channels, n_heads, n_layers,
                     kernel, window):
    layers = []
    for i in range(n_layers):
        r = jax.random.fold_in(rng, i)
        r1, r2 = jax.random.split(r)
        layers.append({
            "attn": init_rel_attention(r1, channels, n_heads, window),
            "ln1": init_layer_norm(channels),
            "ffn": init_ffn(r2, channels, filter_channels, kernel),
            "ln2": init_layer_norm(channels),
        })
    return {"layers": layers}


def transformer(x, mask, p, *, n_heads, window):
    """Post-norm transformer: x = LN(x + attn(x)); x = LN(x + ffn(x))."""
    x = x * mask
    for layer in p["layers"]:
        y = rel_attention(x, mask, layer["attn"], n_heads=n_heads, window=window)
        x = layer_norm(x + y, layer["ln1"])
        y = ffn(x, mask, layer["ffn"])
        x = layer_norm(x + y, layer["ln2"])
    return x * mask


def transformer_seq_parallel(x, mask, p, *, n_heads, window, mesh):
    """The same post-norm encoder stack, SPMD over the mesh's ``seq`` axis.

    Long inputs shard along time: attention runs as a ring
    (:func:`sonata_tpu.parallel.ring.ring_rel_attention_sharded`, exact —
    including the windowed relative embeddings, which only couple
    ring-adjacent blocks since |s−t| ≤ window), and the FFN's kernel-3
    convs see their neighbors' boundary columns via a halo exchange.  All
    other ops are per-position and stay local.  Numerics match
    :func:`transformer` (same math, blockwise softmax).

    ``x: [B, T, C]`` with ``T`` divisible by the seq-axis size.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, SEQ_AXIS
    from ..parallel.ring import halo_exchange, ring_rel_attention_sharded
    from jax import shard_map

    def attn_local(x_loc, mask_loc, lp):
        b, t, c = x_loc.shape
        head = c // n_heads

        def split(u):  # [B, T, C] → [B, H, T, head]
            return u.reshape(b, t, n_heads, head).transpose(0, 2, 1, 3)

        out = ring_rel_attention_sharded(
            split(conv1d(x_loc, lp["q"])),
            split(conv1d(x_loc, lp["k"])),
            split(conv1d(x_loc, lp["v"])),
            mask_loc[..., 0],
            lp["emb_rel_k"][0], lp["emb_rel_v"][0], window=window)
        out = out.transpose(0, 2, 1, 3).reshape(b, t, c)
        return conv1d(out, lp["o"]) * mask_loc

    def conv_halo(x_loc, cp):
        k = cp["w"].shape[0]
        ext = halo_exchange(x_loc, k // 2, k - 1 - k // 2)
        return conv1d(ext, cp, padding=0)

    def inner(x_loc, mask_loc, params):
        x_loc = x_loc * mask_loc
        for layer in params["layers"]:
            y = attn_local(x_loc, mask_loc, layer["attn"])
            x_loc = layer_norm(x_loc + y, layer["ln1"])
            y = conv_halo(x_loc * mask_loc, layer["ffn"]["c1"])
            y = jax.nn.relu(y)
            y = conv_halo(y * mask_loc, layer["ffn"]["c2"]) * mask_loc
            x_loc = layer_norm(x_loc + y, layer["ln2"])
        return x_loc * mask_loc

    spec_x = P(DATA_AXIS, SEQ_AXIS, None)
    fn = shard_map(inner, mesh=mesh, in_specs=(spec_x, spec_x, P()),
                   out_specs=spec_x)
    return fn(x, mask, p)


# ---------------------------------------------------------------------------
# WaveNet block (used by the coupling flow)
# ---------------------------------------------------------------------------

def init_wn(rng, *, hidden, kernel, dilation_rate, n_layers, gin_channels=0):
    in_layers, res_skip = [], []
    for i in range(n_layers):
        r = jax.random.fold_in(rng, i)
        r1, r2 = jax.random.split(r)
        dil = dilation_rate ** i
        in_layers.append(_conv_init(r1, kernel, hidden, 2 * hidden))
        out_ch = 2 * hidden if i < n_layers - 1 else hidden
        res_skip.append(_conv_init(r2, 1, hidden, out_ch))
    p = {"in": in_layers, "res_skip": res_skip}
    if gin_channels:
        p["cond"] = _conv_init(jax.random.fold_in(rng, 999), 1, gin_channels,
                               2 * hidden * n_layers)
    return p


def gate(y):
    """The gated activation ``tanh(a) * sigmoid(b)`` over the two halves
    of a WaveNet pre-activation ``y: [B, T, 2H]`` (conditioning added):
    elementwise, so XLA fuses it into its neighbours."""
    hidden = y.shape[-1] // 2
    return jnp.tanh(y[..., :hidden]) * jax.nn.sigmoid(y[..., hidden:])


def wn(x, mask, p, *, kernel, dilation_rate, n_layers, g=None, conv=None):
    """Non-causal WaveNet: dilated convs, gated tanh units, residual+skip.

    ``x: [B, T, H]``; ``g: [B, 1, gin]`` speaker conditioning or None.
    ``conv`` overrides the dilated conv primitive (sequence-sharded
    callers inject a halo-exchange version); pointwise convs never need
    halos and stay plain.
    """
    conv = conv or conv1d
    hidden = x.shape[-1]
    output = jnp.zeros_like(x)
    if g is not None and "cond" in p:
        g_all = conv1d(g, p["cond"])  # [B, 1, 2*H*n_layers]
    for i in range(n_layers):
        x_in = conv(x, p["in"][i], dilation=dilation_rate ** i)
        g_l = None
        if g is not None and "cond" in p:
            g_l = lax.dynamic_slice_in_dim(g_all, i * 2 * hidden, 2 * hidden, axis=2)
        acts = gate(x_in if g_l is None else x_in + g_l)
        rs = conv1d(acts, p["res_skip"][i])
        if i < n_layers - 1:
            x = (x + rs[..., :hidden]) * mask
            output = output + rs[..., hidden:]
        else:
            output = output + rs
    return output * mask


# ---------------------------------------------------------------------------
# DDSConv — dilated depth-separable convs (duration predictor backbone)
# ---------------------------------------------------------------------------

def init_dds_conv(rng, *, channels, kernel, n_layers):
    layers = []
    for i in range(n_layers):
        r = jax.random.fold_in(rng, i)
        r1, r2 = jax.random.split(r)
        layers.append({
            # depthwise stored [K, 1, C] and applied with feature_group_count
            "dw": {"w": _normal(r1, (kernel, 1, channels),
                                1.0 / math.sqrt(kernel)),
                   "b": jnp.zeros((channels,))},
            "pw": _conv_init(r2, 1, channels, channels),
            "ln1": init_layer_norm(channels),
            "ln2": init_layer_norm(channels),
        })
    return {"layers": layers}


def dds_conv(x, mask, p, *, kernel: int, g=None):
    if g is not None:
        x = x + g
    c = x.shape[-1]
    for i, layer in enumerate(p["layers"]):
        dilation = kernel ** i
        k_eff = (kernel - 1) * dilation + 1
        pad = k_eff // 2
        y = lax.conv_general_dilated(
            x * mask, layer["dw"]["w"], window_strides=(1,),
            padding=[(pad, k_eff - 1 - pad)], rhs_dilation=(dilation,),
            dimension_numbers=("NHC", "HIO", "NHC"),
            feature_group_count=c,
        ) + layer["dw"]["b"]
        y = jax.nn.gelu(layer_norm(y, layer["ln1"]))
        y = conv1d(y, layer["pw"])
        y = jax.nn.gelu(layer_norm(y, layer["ln2"]))
        x = x + y
    return x * mask


# ---------------------------------------------------------------------------
# rational-quadratic spline (inverse mode) — ConvFlow transform
# ---------------------------------------------------------------------------

DEFAULT_MIN_BIN_WIDTH = 1e-3
DEFAULT_MIN_BIN_HEIGHT = 1e-3
DEFAULT_MIN_DERIVATIVE = 1e-3


def rational_quadratic_spline_inverse(
    y, unnorm_widths, unnorm_heights, unnorm_derivs, *, tail_bound: float
):
    """Inverse pass of an unconstrained monotonic rational-quadratic spline
    (Durkan et al., Neural Spline Flows).  Identity outside
    ``[-tail_bound, tail_bound]``.

    All inputs broadcast elementwise with a trailing ``num_bins`` dim on the
    parameter tensors.  Fully vectorized; no data-dependent control flow, so
    it jits to a single fused XLA computation.
    """
    num_bins = unnorm_widths.shape[-1]
    inside = (y >= -tail_bound) & (y <= tail_bound)

    widths = jax.nn.softmax(unnorm_widths, axis=-1)
    widths = DEFAULT_MIN_BIN_WIDTH + (1 - DEFAULT_MIN_BIN_WIDTH * num_bins) * widths
    cumwidths = jnp.cumsum(widths, axis=-1)
    cumwidths = jnp.pad(cumwidths, [(0, 0)] * (cumwidths.ndim - 1) + [(1, 0)])
    cumwidths = (2 * tail_bound) * cumwidths - tail_bound
    widths = cumwidths[..., 1:] - cumwidths[..., :-1]

    derivs = DEFAULT_MIN_DERIVATIVE + jax.nn.softplus(unnorm_derivs)
    # boundary derivatives pinned to 1 (linear tails)
    pad_val = math.log(math.exp(1 - DEFAULT_MIN_DERIVATIVE) - 1)
    derivs = jnp.concatenate(
        [jnp.full_like(derivs[..., :1], DEFAULT_MIN_DERIVATIVE
                       + jax.nn.softplus(jnp.float32(pad_val))),
         derivs,
         jnp.full_like(derivs[..., :1], DEFAULT_MIN_DERIVATIVE
                       + jax.nn.softplus(jnp.float32(pad_val)))],
        axis=-1,
    )

    heights = jax.nn.softmax(unnorm_heights, axis=-1)
    heights = DEFAULT_MIN_BIN_HEIGHT + (1 - DEFAULT_MIN_BIN_HEIGHT * num_bins) * heights
    cumheights = jnp.cumsum(heights, axis=-1)
    cumheights = jnp.pad(cumheights, [(0, 0)] * (cumheights.ndim - 1) + [(1, 0)])
    cumheights = (2 * tail_bound) * cumheights - tail_bound
    heights = cumheights[..., 1:] - cumheights[..., :-1]

    y_in = jnp.clip(y, -tail_bound, tail_bound)
    # locate bin by cumheights (inverse mode): one-hot over bins
    idx = jnp.sum((y_in[..., None] >= cumheights[..., :-1]).astype(jnp.int32),
                  axis=-1) - 1
    idx = jnp.clip(idx, 0, num_bins - 1)

    def gather(t):
        return jnp.take_along_axis(t, idx[..., None], axis=-1)[..., 0]

    in_cumwidths = gather(cumwidths[..., :-1])
    in_widths = gather(widths)
    in_cumheights = gather(cumheights[..., :-1])
    in_heights = gather(heights)
    in_delta = in_heights / in_widths
    in_d = gather(derivs[..., :-1])
    in_d_plus = gather(derivs[..., 1:])

    # solve the quadratic for xi (Durkan et al. eq. 6-8, inverse)
    rel_y = y_in - in_cumheights
    term = rel_y * (in_d + in_d_plus - 2 * in_delta)
    a = in_heights * (in_delta - in_d) + term
    b = in_heights * in_d - term
    c = -in_delta * rel_y
    disc = b * b - 4 * a * c
    disc = jnp.maximum(disc, 0.0)
    xi = (2 * c) / (-b - jnp.sqrt(disc))
    xi = jnp.clip(xi, 0.0, 1.0)
    x_val = xi * in_widths + in_cumwidths

    # log|det d y / d x| (forward direction), negated by the caller if needed
    denom = in_delta + (in_d + in_d_plus - 2 * in_delta) * xi * (1 - xi)
    nom = in_delta ** 2 * (
        in_d_plus * xi ** 2 + 2 * in_delta * xi * (1 - xi) + in_d * (1 - xi) ** 2
    )
    logabsdet = jnp.log(jnp.maximum(nom, 1e-12)) - 2 * jnp.log(
        jnp.maximum(denom, 1e-12)
    )

    x_out = jnp.where(inside, x_val, y)
    logabsdet = jnp.where(inside, logabsdet, 0.0)
    return x_out, logabsdet
