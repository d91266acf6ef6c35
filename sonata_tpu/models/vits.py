"""Piper-flavor VITS, implemented natively in JAX.

The reference executes this model as a black-box ONNX graph through
onnxruntime (``crates/sonata/models/piper/src/lib.rs:342-399`` single-graph;
``:537-574`` + ``:736-762`` encoder/decoder split).  Here the graph is
re-implemented as pure functions so XLA compiles it straight to TPU:

- ``encode_text``    — text encoder + stochastic duration predictor
                       → frame durations and phoneme-level priors.
- ``acoustics``      — length regulation (generate_path), prior sampling,
                       residual-coupling flow (reverse) → latent ``z``.
- ``decode``         — HiFi-GAN generator: ``z`` → waveform.
- ``infer``          — the composition, one jittable graph.

The encode/decode split mirrors the reference's streaming
``VitsStreamingModel`` contract (``EncoderOutputs{z, y_mask, g}`` →
decoder slices of ``z``, ``piper/src/lib.rs:671-762``), but the split point
is chosen for TPU: everything with data-dependent sizing (durations) lives
in ``encode_text``; ``acoustics``/``decode`` take static frame buckets so
each bucket compiles once and is reused.

RNG is explicit: the reference's ``scales``-driven noise is generated inside
the ONNX graph; here the caller passes a ``jax.random`` key so batched
synthesis draws independent noise per sentence (SURVEY §7 "RNG semantics").

All tensors are ``[batch, time, channels]``; masks ``[B, T, 1]``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .config import VitsHyperParams
from . import modules as m

Params = dict


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_text_encoder(rng, hp: VitsHyperParams, n_vocab: int) -> Params:
    r_emb, r_enc, r_proj = jax.random.split(rng, 3)
    return {
        "emb": jax.random.normal(r_emb, (n_vocab, hp.hidden_channels))
        * (hp.hidden_channels ** -0.5),
        "encoder": m.init_transformer(
            r_enc, channels=hp.hidden_channels,
            filter_channels=hp.filter_channels, n_heads=hp.n_heads,
            n_layers=hp.n_layers, kernel=hp.kernel_size, window=hp.attn_window,
        ),
        "proj": m._conv_init(r_proj, 1, hp.hidden_channels, 2 * hp.inter_channels),
    }


def init_duration_predictor(rng, hp: VitsHyperParams, gin: int) -> Params:
    rngs = jax.random.split(rng, 8)
    filt = hp.dp_filter_channels
    p: Params = {
        "pre": m._conv_init(rngs[0], 1, hp.hidden_channels, filt),
        "convs": m.init_dds_conv(rngs[1], channels=filt,
                                 kernel=hp.dp_kernel_size, n_layers=3),
        "proj": m._conv_init(rngs[2], 1, filt, filt),
        "affine": {"m": jnp.zeros((2,)), "logs": jnp.zeros((2,))},
        "flows": [],
    }
    if gin:
        p["cond"] = m._conv_init(rngs[3], 1, gin, filt)
    for i in range(hp.dp_n_flows):
        r = jax.random.fold_in(rngs[4], i)
        r1, r2, r3 = jax.random.split(r, 3)
        n_out = 3 * hp.dp_num_bins - 1
        p["flows"].append({
            "pre": m._conv_init(r1, 1, 1, filt),
            "convs": m.init_dds_conv(r2, channels=filt,
                                     kernel=hp.dp_kernel_size, n_layers=3),
            "proj": {"w": jnp.zeros((1, filt, n_out)),
                     "b": jnp.zeros((n_out,))},  # zero-init → identity start
        })
    return p


def init_flow(rng, hp: VitsHyperParams, gin: int) -> Params:
    half = hp.inter_channels // 2
    layers = []
    for i in range(hp.flow_n_layers):
        r = jax.random.fold_in(rng, i)
        r1, r2, r3 = jax.random.split(r, 3)
        layers.append({
            "pre": m._conv_init(r1, 1, half, hp.hidden_channels),
            "wn": m.init_wn(r2, hidden=hp.hidden_channels,
                            kernel=hp.flow_kernel_size, dilation_rate=1,
                            n_layers=hp.flow_wn_layers, gin_channels=gin),
            "post": {"w": jnp.zeros((1, hp.hidden_channels, half)),
                     "b": jnp.zeros((half,))},  # zero-init (identity start)
        })
    return {"layers": layers}


def init_generator(rng, hp: VitsHyperParams, gin: int) -> Params:
    rngs = jax.random.split(rng, 4)
    ch0 = hp.upsample_initial_channel
    p: Params = {
        "conv_pre": m._conv_init(rngs[0], 7, hp.inter_channels, ch0),
        "ups": [],
        "resblocks": [],
        "conv_post": m._conv_init(rngs[1], 7, ch0 // (2 ** len(hp.upsample_rates)), 1),
    }
    if gin:
        p["cond"] = m._conv_init(rngs[2], 1, gin, ch0)
    for i, (r_up, k_up) in enumerate(zip(hp.upsample_rates, hp.upsample_kernel_sizes)):
        r = jax.random.fold_in(rngs[3], i)
        c_in, c_out = ch0 // (2 ** i), ch0 // (2 ** (i + 1))
        p["ups"].append(m._conv_init(r, k_up, c_in, c_out))
        for j, (k_res, dils) in enumerate(
            zip(hp.resblock_kernel_sizes, hp.resblock_dilation_sizes)
        ):
            rr = jax.random.fold_in(r, 100 + j)
            block = {"convs1": [], "convs2": []}
            for di, d in enumerate(dils):
                ra = jax.random.fold_in(rr, di)
                ra1, ra2 = jax.random.split(ra)
                block["convs1"].append(m._conv_init(ra1, k_res, c_out, c_out))
                block["convs2"].append(m._conv_init(ra2, k_res, c_out, c_out))
            p["resblocks"].append(block)
    return p


def init_vits(rng, hp: VitsHyperParams, *, n_vocab: int,
              n_speakers: int = 1) -> Params:
    rngs = jax.random.split(rng, 5)
    gin = hp.gin_channels if n_speakers > 1 else 0
    p: Params = {
        "enc_p": init_text_encoder(rngs[0], hp, n_vocab),
        "dp": init_duration_predictor(rngs[1], hp, gin),
        "flow": init_flow(rngs[2], hp, gin),
        "dec": init_generator(rngs[3], hp, gin),
    }
    if n_speakers > 1:
        p["emb_g"] = jax.random.normal(rngs[4], (n_speakers, hp.gin_channels)) * 0.02
    return p


# ---------------------------------------------------------------------------
# stage 1: text encoder + stochastic duration predictor
# ---------------------------------------------------------------------------

def sequence_mask(lengths, max_len: int):
    """[B] lengths → [B, max_len, 1] float mask."""
    idx = jnp.arange(max_len)[None, :]
    return (idx < lengths[:, None]).astype(jnp.float32)[..., None]


def per_row_normal(rng, shape):
    """Standard-normal draws with **per-row** keys: row ``i`` of the
    ``[B, ...]`` output is drawn from ``fold_in(rng, i)`` over the
    per-row shape alone.

    A single batch-shaped draw makes every row's values a function of the
    whole batch shape — so padding the batch (mesh data-axis rounding, a
    coalesced group's dummy rows) silently changes every *real* row's
    noise, and sharded vs unsharded dispatches of the same sentence
    diverge (the 6 former test_parallel xfails).  Per-row keys make a
    row's draw depend only on (key, row index, row shape): batch
    neighbors and padding rows cannot perturb it, which is also the
    correctness contract continuous batching needs — a request's audio
    must not depend on whatever shared its dispatch.  Row shapes stay
    bucket-stable because both the text and frame axes are bucketed
    identically with or without a mesh.
    """
    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
        jnp.arange(shape[0]))
    return jax.vmap(lambda k: jax.random.normal(k, shape[1:]))(keys)


def text_encoder(p: Params, hp: VitsHyperParams, ids, x_mask, mesh=None):
    x = p["emb"][ids] * math.sqrt(hp.hidden_channels)  # [B, T, H]
    seq = 0 if mesh is None else mesh.shape.get("seq", 1)
    if mesh is not None and seq > 1 and x.shape[1] % seq == 0:
        # sequence parallelism: ring attention + halo convs over the
        # mesh's seq axis (long inputs shard along time)
        x = m.transformer_seq_parallel(x, x_mask, p["encoder"],
                                       n_heads=hp.n_heads,
                                       window=hp.attn_window, mesh=mesh)
    else:
        x = m.transformer(x, x_mask, p["encoder"], n_heads=hp.n_heads,
                          window=hp.attn_window)
    stats = m.conv1d(x, p["proj"]) * x_mask
    m_p, logs_p = jnp.split(stats, 2, axis=-1)
    return x, m_p, logs_p


@jax.named_scope("duration_predictor")
def duration_predictor_reverse(p: Params, hp: VitsHyperParams, x, x_mask,
                               rng, noise_w, g=None):
    """Stochastic duration predictor, inference (reverse-flow) path → logw.

    Flow order replicates VITS inference exactly, including the quirk that
    the first ConvFlow is skipped at inference time (the exported Piper
    graphs bake this in, so weight-parity requires it).
    """
    h = m.conv1d(x, p["pre"])
    if g is not None and "cond" in p:
        h = h + m.conv1d(g, p["cond"])
    h = m.dds_conv(h, x_mask, p["convs"], kernel=hp.dp_kernel_size)
    h = m.conv1d(h, p["proj"]) * x_mask

    b, t, _ = x.shape
    # noise_w may be a scalar or a per-row [B] vector (coalesced batches
    # carry per-request scales)
    noise_w = jnp.reshape(jnp.asarray(noise_w, jnp.float32), (-1, 1, 1))
    z = per_row_normal(rng, (b, t, 2)) * noise_w * x_mask

    # reversed flow stack: Flip/ConvFlow pairs (skipping ConvFlow #0), then
    # the elementwise affine
    for i in range(hp.dp_n_flows - 1, 0, -1):
        z = z[..., ::-1]  # Flip
        z = _conv_flow_reverse(p["flows"][i], hp, z, x_mask, h)
    z = z[..., ::-1]  # Flip preceding the skipped ConvFlow #0
    # ElementwiseAffine reverse: x = (z - m) * exp(-logs)
    aff = p["affine"]
    z = (z - aff["m"]) * jnp.exp(-aff["logs"]) * x_mask
    logw = z[..., 0:1]
    return logw


def _conv_flow_reverse(pf: Params, hp: VitsHyperParams, z, mask, g):
    z0, z1 = z[..., 0:1], z[..., 1:2]
    h = m.conv1d(z0, pf["pre"])
    h = m.dds_conv(h, mask, pf["convs"], kernel=hp.dp_kernel_size, g=g)
    h = m.conv1d(h, pf["proj"]) * mask  # [B, T, 3*bins-1]
    nb = hp.dp_num_bins
    filt = hp.dp_filter_channels
    uw = h[..., :nb] / math.sqrt(filt)
    uh = h[..., nb:2 * nb] / math.sqrt(filt)
    ud = h[..., 2 * nb:]
    x1, _ = m.rational_quadratic_spline_inverse(
        z1[..., 0], uw, uh, ud, tail_bound=hp.dp_tail_bound
    )
    return jnp.concatenate([z0, x1[..., None] * mask], axis=-1)


@jax.named_scope("encode_text")
def encode_text(p: Params, hp: VitsHyperParams, ids, x_lengths, rng, *,
                noise_w: float, length_scale: float, sid=None, mesh=None):
    """ids [B, T] → (m_p, logs_p [B, T, C], durations w_ceil [B, T], g).

    Everything whose output size depends on data (durations) is computed
    here; downstream stages take a static frame budget.
    """
    x_mask = sequence_mask(x_lengths, ids.shape[1])
    g = None
    if sid is not None and "emb_g" in p:
        g = p["emb_g"][sid][:, None, :]  # [B, 1, gin]
    x, m_p, logs_p = text_encoder(p["enc_p"], hp, ids, x_mask, mesh=mesh)
    logw = duration_predictor_reverse(p["dp"], hp, x, x_mask, rng,
                                      noise_w, g=g)
    length_scale = jnp.reshape(jnp.asarray(length_scale, jnp.float32),
                               (-1, 1, 1))  # scalar or per-row [B]
    w = jnp.exp(logw) * x_mask * length_scale
    w_ceil = jnp.ceil(w)[..., 0]  # [B, T]
    return m_p, logs_p, w_ceil, x_mask, g


# ---------------------------------------------------------------------------
# stage 2: length regulation + prior + flow reverse
# ---------------------------------------------------------------------------

def generate_path(w_ceil, x_mask, max_frames: int):
    """Monotonic alignment path from durations.

    ``w_ceil: [B, T]`` → ``path: [B, T, F]`` with ``path[b, t, f] = 1`` iff
    frame ``f`` belongs to phoneme ``t``.  Pure broadcasting — no scatter,
    no dynamic shapes; the MXU eats the downstream einsum.

    The exclusive prefix sum is ``cum - w`` (exact: durations are small
    integers), NOT the textbook zero-pad-and-slice concatenate.  On a
    mesh whose seq axis shards the T dimension, XLA's SPMD partitioner
    miscompiles the slice+concat shift (observed on jax 0.4.37: path
    rows off by one frame vs the unsharded graph for identical
    ``w_ceil`` — the former test_parallel mesh-numeric failures), while
    the subtraction form partitions correctly under every sharding.
    """
    w = w_ceil * x_mask[..., 0]
    cum = jnp.cumsum(w, axis=1)  # [B, T]
    f = jnp.arange(max_frames)[None, None, :]
    upper = f < cum[..., None]
    lower = f >= (cum - w)[..., None]
    return (upper & lower).astype(jnp.float32)


@jax.named_scope("acoustics")
def acoustics(p: Params, hp: VitsHyperParams, m_p, logs_p, w_ceil, x_mask,
              rng, *, noise_scale: float, max_frames: int, g=None,
              mesh=None):
    """Durations + priors → latent ``z`` [B, F, C] and frame mask."""
    y_lengths = jnp.clip(jnp.sum(w_ceil, axis=1), 1, max_frames).astype(jnp.int32)
    y_mask = sequence_mask(y_lengths, max_frames)  # [B, F, 1]
    path = generate_path(w_ceil, x_mask, max_frames)  # [B, T, F]
    m_p_f = jnp.einsum("btf,btc->bfc", path, m_p)
    logs_p_f = jnp.einsum("btf,btc->bfc", path, logs_p)
    noise = per_row_normal(rng, m_p_f.shape)
    noise_scale = jnp.reshape(jnp.asarray(noise_scale, jnp.float32),
                              (-1, 1, 1))  # scalar or per-row [B]
    z_p = m_p_f + noise * jnp.exp(logs_p_f) * noise_scale
    if _use_seq_parallel(mesh, max_frames, hp):
        from .seq_parallel import flow_reverse_sp

        z = flow_reverse_sp(p["flow"], hp, z_p, y_mask, mesh, g=g)
    else:
        z = flow_reverse(p["flow"], hp, z_p, y_mask, g=g)
    return z * y_mask, y_mask, y_lengths


def _use_seq_parallel(mesh, frames: int, hp: VitsHyperParams) -> bool:
    """Frame-domain ops shard over the seq axis when the mesh has one and
    the per-shard frame count leaves room for every conv halo (the halos
    are neighbor-only, so each stage's local length must cover its
    largest receptive-field reach — derived from hp, not hard-coded)."""
    if mesh is None:
        return False
    if mesh.shape.get("model", 1) > 1:
        # tensor parallelism owns the flow/decoder when the model axis is
        # active: the sp shard_maps take params with replicated in_specs,
        # which would force an all-gather of the model-sharded decoder
        # weights on every dispatch and then compute the full channel
        # range redundantly on each tp chip — worse than either axis
        # alone.  Ring attention (text domain) still rides the seq axis.
        return False
    seq = mesh.shape.get("seq", 1)
    if seq <= 1 or frames % seq:
        return False
    from .seq_parallel import min_local_frames

    return frames // seq >= min_local_frames(hp)


@jax.named_scope("flow_reverse")
def flow_reverse(pf: Params, hp: VitsHyperParams, z, mask, g=None,
                 conv=None):
    """``conv``: the seq-parallel caller, inside its ``shard_map``,
    injects a halo-exchange convolution."""
    half = hp.inter_channels // 2
    for layer in reversed(pf["layers"]):
        z = z[..., ::-1]  # Flip (reverse order: undo the flip first)
        z0, z1 = z[..., :half], z[..., half:]
        h = m.conv1d(z0, layer["pre"]) * mask
        h = m.wn(h, mask, layer["wn"], kernel=hp.flow_kernel_size,
                 dilation_rate=1, n_layers=hp.flow_wn_layers, g=g,
                 conv=conv)
        mean = m.conv1d(h, layer["post"]) * mask
        z1 = (z1 - mean) * mask  # mean-only coupling, reverse
        z = jnp.concatenate([z0, z1], axis=-1)
    return z


# ---------------------------------------------------------------------------
# stage 3: HiFi-GAN decoder
# ---------------------------------------------------------------------------

@jax.named_scope("decode")
def decode(p: Params, hp: VitsHyperParams, z, g=None, mesh=None,
           compute_dtype=None):
    """Latent ``z`` [B, F, C] → waveform [B, F * hop].

    The FLOPs live here (upsampling convs); channels shrink as time grows,
    and from 128 channels down the stages run time-folded
    (:func:`decode_fold`), keeping every conv a matmul over a full
    128-wide channel dim.  With a seq-axis mesh the frames (and output
    samples) shard across chips (:mod:`.seq_parallel`).

    ``compute_dtype``: optional reduced-precision policy for the conv
    stack (``jnp.bfloat16`` keeps the MXU in its native mode — one
    hardware pass instead of three for float32).  Weights and activations
    are cast on entry; the output returns to float32 before ``tanh`` so
    the final waveform (and its downstream i16 quantization) stays
    full-precision at the last nonlinearity.
    """
    if _use_seq_parallel(mesh, z.shape[1], hp):
        from .seq_parallel import decode_sp

        return decode_sp(p, hp, z, mesh, g=g, compute_dtype=compute_dtype)
    return decode_with(p, hp, z, g=g, compute_dtype=compute_dtype)


def decode_fold(pd: Params, hp: VitsHyperParams, frames: int,
                mesh=None) -> list[int]:
    """Per upsample stage, how many time steps its activations fold into
    the channel axis (:func:`modules.fold_factor`; 1 = unfolded), for a
    decoder over ``frames`` frames (:func:`decode` on ``mesh``: one
    shard's, where the frames shard).  From shapes alone: the stage's
    channels (its bias, so a quantized tree reads the same), its length,
    and whether its transposed convolution has the sub-pixel geometry
    that produces the folded form."""
    if _use_seq_parallel(mesh, frames, hp):
        frames //= mesh.shape["seq"]
    folds, length = [], frames
    for up, r_up, k_up in zip(pd["ups"], hp.upsample_rates,
                              hp.upsample_kernel_sizes):
        length *= r_up
        subpixel = r_up > 1 and (k_up - r_up) % 2 == 0
        folds.append(m.fold_factor(up["b"].shape[0], length)
                     if subpixel else 1)
    return folds


def decode_with(p: Params, hp: VitsHyperParams, z, g=None, conv=None,
                tconv=None, compute_dtype=None):
    """:func:`decode` body with injectable conv primitives — the
    sequence-sharded path passes halo-exchange versions, so the model
    math exists exactly once.  A time-folded stage (:func:`decode_fold`)
    is plain ``SAME`` convolutions too and goes through the same ``conv``;
    ``tconv`` serves the unfolded stages."""
    conv = conv or m.conv1d
    tconv = tconv or (lambda x, p_, *, stride, padding:
                      m.conv_transpose1d(x, p_, stride=stride,
                                         padding=padding))
    from .decode_opts import dequantize_decoder

    # int8 weight-only arm (SONATA_DECODE_QUANT): quantized conv weights
    # rescale to f32 here, inside the device program — a plain f32 tree
    # passes through untouched
    pd = dequantize_decoder(p["dec"])
    if compute_dtype is not None:
        # on-device cast of the decoder weights: pure HBM traffic (~0.1 ms
        # for the full stack), repaid many times over by MXU-native convs
        pd = jax.tree_util.tree_map(
            lambda a: a.astype(compute_dtype), pd)
        z = z.astype(compute_dtype)
        if g is not None:
            g = g.astype(compute_dtype)
    folds = decode_fold(pd, hp, z.shape[1])
    # the stages carry names into the compiled program (metadata only), so
    # a device trace is read by stage: pre, ups1..upsN (an upsampling
    # convolution and its residual blocks), post
    with jax.named_scope("pre"):
        x = conv(z, pd["conv_pre"])
        if g is not None and "cond" in pd:
            x = x + m.conv1d(g, pd["cond"])
    n_kernels = len(hp.resblock_kernel_sizes)
    r = 1  # x is [B, T / r, r * C] from here on
    for i, (r_up, k_up) in enumerate(zip(hp.upsample_rates, hp.upsample_kernel_sizes)):
        with jax.named_scope(f"ups{i + 1}"):
            x = jax.nn.leaky_relu(x, m.LRELU_SLOPE)
            pad = (k_up - r_up) // 2
            if folds[i] == 1:
                x = tconv(m.fold_time(x, r, 1), pd["ups"][i], stride=r_up,
                          padding=pad)
            else:
                # the sub-pixel convolution's own output is the folded
                # form: with the published rates (8, 8, 2, 2) the stages
                # under 128 channels follow each other without a reshape
                x = conv(x, m.fold_conv_transpose(
                    pd["ups"][i], r, stride=r_up, padding=pad))
                x = m.fold_time(x, r * r_up, folds[i])
            r = folds[i]
            xs = None
            for j in range(n_kernels):
                block = pd["resblocks"][i * n_kernels + j]
                y = _resblock1(block, x, hp.resblock_kernel_sizes[j],
                               hp.resblock_dilation_sizes[j], conv=conv,
                               fold=r)
                xs = y if xs is None else xs + y
            x = xs / n_kernels
    with jax.named_scope("post"):
        x = jax.nn.leaky_relu(x, m.LRELU_SLOPE)
        x = _conv_folded(conv, x, pd["conv_post"], r)  # [B, T / r, r]
        x = x.reshape(x.shape[0], -1)  # the one unfold: [B, samples]
        return jnp.tanh(x.astype(jnp.float32))


def _conv_folded(conv, x, p: Params, fold: int, dilation: int = 1):
    """``conv`` of ``p`` on an activation folded by ``fold``."""
    if fold == 1:
        return conv(x, p, dilation=dilation)
    return conv(x, m.fold_conv(p, fold, dilation=dilation))


def _resblock1(block: Params, x, kernel: int, dilations, conv=None,
               fold: int = 1):
    conv = conv or m.conv1d
    for c1, c2, d in zip(block["convs1"], block["convs2"], dilations):
        y = jax.nn.leaky_relu(x, m.LRELU_SLOPE)
        y = _conv_folded(conv, y, c1, fold, d)
        y = jax.nn.leaky_relu(y, m.LRELU_SLOPE)
        y = _conv_folded(conv, y, c2, fold)
        x = x + y
    return x


# ---------------------------------------------------------------------------
# full graph
# ---------------------------------------------------------------------------

def infer(p: Params, hp: VitsHyperParams, ids, x_lengths, rng, *,
          noise_scale: float = 0.667, length_scale: float = 1.0,
          noise_w: float = 0.8, max_frames: int = 1024, sid=None):
    """Single-graph inference: ids → waveform.

    Matches the reference's single-ONNX contract — inputs
    ``(input [B,T], input_lengths [B], scales, sid?)``
    (``piper/src/lib.rs:345-368``) — with explicit RNG and a static frame
    budget ``max_frames`` (the dynamic-shape boundary the ONNX graph hides).

    Returns (wav [B, max_frames*hop], wav_lengths [B] in samples).
    """
    rng_dur, rng_noise = jax.random.split(rng)
    m_p, logs_p, w_ceil, x_mask, g = encode_text(
        p, hp, ids, x_lengths, rng_dur, noise_w=noise_w,
        length_scale=length_scale, sid=sid,
    )
    z, y_mask, y_lengths = acoustics(
        p, hp, m_p, logs_p, w_ceil, x_mask, rng_noise,
        noise_scale=noise_scale, max_frames=max_frames, g=g,
    )
    wav = decode(p, hp, z, g=g)
    return wav, y_lengths * hp.hop_length
