"""Plain reference of the Piper-flavour VITS inference graph.

Straightforward ``jax.numpy`` in float32 after the published description
(Kim et al. 2021, "Conditional Variational Autoencoder with Adversarial
Learning for End-to-End Text-to-Speech"; Piper's ``vits`` export): text
encoder with windowed relative attention, stochastic duration predictor run
in reverse, monotonic length regulation, mean-only coupling flow in reverse
with WaveNet blocks, HiFi-GAN generator.  No kernels, no staging, no
quantisation, nothing imported from the program.  Tensors are
``[batch, time, channels]``; rows of different lengths are padded and
masked, which is what the published graph does too.

Departures from the published inference graph, all because the stock RPCs
expose no seed: the noise inputs are arguments (``None`` means zero, which
is what the cells' voices are configured for), and the durations used for
length regulation are an argument of :func:`synthesize`, so that the
reference can be run over the durations that were served.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

LRELU = 0.1


def conv(x, p, dilation: int = 1, groups: int = 1):
    """'Same' 1-D convolution; weight ``[K, C_in / groups, C_out]``."""
    k = p["w"].shape[0]
    span = (k - 1) * dilation
    y = lax.conv_general_dilated(
        x, p["w"], (1,), [(span // 2, span - span // 2)],
        rhs_dilation=(dilation,), dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=groups)
    return y + p["b"]


def conv_transpose(x, p, stride: int):
    """torch ``ConvTranspose1d`` with padding ``(K - stride) // 2``."""
    k = p["w"].shape[0]
    pad = k - 1 - (k - stride) // 2
    y = lax.conv_general_dilated(
        x, jnp.flip(p["w"], 0), (1,), [(pad, pad)], lhs_dilation=(stride,),
        dimension_numbers=("NHC", "HIO", "NHC"))
    return y + p["b"]


def layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["gamma"] + p["beta"]


def mask_of(lengths, size: int):
    return (jnp.arange(size)[None, :] < lengths[:, None]).astype(
        jnp.float32)[..., None]


# -- text encoder ----------------------------------------------------------

def _rel_table(emb, window: int, t: int):
    pad = max(t - window - 1, 0)
    start = max(window + 1 - t, 0)
    emb = jnp.pad(emb, ((0, 0), (pad, pad), (0, 0)))
    return emb[:, start:start + 2 * t - 1]


def _rel_to_abs(x):
    b, t, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 1))).reshape(b, 2 * t * t)
    x = jnp.pad(x, ((0, 0), (0, t - 1))).reshape(b, t + 1, 2 * t - 1)
    return x[:, :t, t - 1:]


def _abs_to_rel(x):
    b, t, _ = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, t - 1))).reshape(b, t * (2 * t - 1))
    x = jnp.pad(x, ((0, 0), (t, 0))).reshape(b, t, 2 * t)
    return x[:, :, 1:]


def attention(x, mask, p, n_heads: int, window: int):
    b, t, c = x.shape
    head = c // n_heads

    def heads(u):
        return u.reshape(b, t, n_heads, head).transpose(0, 2, 1, 3).reshape(
            b * n_heads, t, head)

    q = heads(conv(x, p["q"])) * head ** -0.5
    k, v = heads(conv(x, p["k"])), heads(conv(x, p["v"]))
    scores = jnp.einsum("btd,bsd->bts", q, k)
    rel_k = _rel_table(p["emb_rel_k"], window, t)
    scores = scores + _rel_to_abs(jnp.einsum("btd,msd->bts", q, rel_k))
    pair = mask[:, None, :, 0] * mask[:, :, None, 0]
    pair = jnp.repeat(pair, n_heads, axis=0).reshape(b * n_heads, t, t)
    weights = jax.nn.softmax(jnp.where(pair > 0, scores, -1e4), axis=-1)
    out = jnp.einsum("bts,bsd->btd", weights, v)
    rel_v = _rel_table(p["emb_rel_v"], window, t)
    out = out + jnp.einsum("btm,bmd->btd", _abs_to_rel(weights), rel_v)
    out = out.reshape(b, n_heads, t, head).transpose(0, 2, 1, 3).reshape(
        b, t, c)
    return conv(out, p["o"]) * mask


def text_encoder(p, dims, ids, mask):
    x = p["emb"][ids] * math.sqrt(dims["hidden_channels"]) * mask
    for layer in p["encoder"]["layers"]:
        y = attention(x, mask, layer["attn"], dims["n_heads"],
                      dims["attn_window"])
        x = layer_norm(x + y, layer["ln1"])
        y = jax.nn.relu(conv(x * mask, layer["ffn"]["c1"]))
        y = conv(y * mask, layer["ffn"]["c2"]) * mask
        x = layer_norm(x + y, layer["ln2"])
    x = x * mask
    stats = conv(x, p["proj"]) * mask
    m_p, logs_p = jnp.split(stats, 2, axis=-1)
    return x, m_p, logs_p


# -- stochastic duration predictor, reverse --------------------------------

def dds_conv(x, mask, p, kernel: int, g=None):
    if g is not None:
        x = x + g
    c = x.shape[-1]
    for i, layer in enumerate(p["layers"]):
        y = conv(x * mask, layer["dw"], dilation=kernel ** i, groups=c)
        y = jax.nn.gelu(layer_norm(y, layer["ln1"]))
        y = conv(y, layer["pw"])
        y = jax.nn.gelu(layer_norm(y, layer["ln2"]))
        x = x + y
    return x * mask


def spline_inverse(y, widths, heights, derivs, tail: float):
    """Inverse of the unconstrained rational-quadratic spline (Durkan et
    al. 2019), identity outside ``[-tail, tail]``."""
    bins = widths.shape[-1]
    lo = 1e-3

    def knots(u):
        u = lo + (1 - lo * bins) * jax.nn.softmax(u, axis=-1)
        cum = jnp.pad(jnp.cumsum(u, -1), [(0, 0)] * (u.ndim - 1) + [(1, 0)])
        cum = 2 * tail * cum - tail
        return cum, cum[..., 1:] - cum[..., :-1]

    cumw, w = knots(widths)
    cumh, h = knots(heights)
    edge = jnp.full_like(derivs[..., :1], 1.0)  # linear tails
    d = jnp.concatenate([edge, lo + jax.nn.softplus(derivs), edge], -1)

    inside = (y >= -tail) & (y <= tail)
    yc = jnp.clip(y, -tail, tail)
    idx = jnp.clip(jnp.sum(yc[..., None] >= cumh[..., :-1], -1) - 1, 0,
                   bins - 1)

    def at(t):
        return jnp.take_along_axis(t, idx[..., None], -1)[..., 0]

    x0, wk, y0, hk = at(cumw[..., :-1]), at(w), at(cumh[..., :-1]), at(h)
    dk, dk1 = at(d[..., :-1]), at(d[..., 1:])
    s = hk / wk
    rel = yc - y0
    term = rel * (dk + dk1 - 2 * s)
    a = hk * (s - dk) + term
    b = hk * dk - term
    c = -s * rel
    disc = jnp.maximum(b * b - 4 * a * c, 0.0)
    xi = jnp.clip(2 * c / (-b - jnp.sqrt(disc)), 0.0, 1.0)
    return jnp.where(inside, xi * wk + x0, y)


def log_durations(p, dims, x, mask, g=None, eps=None, noise_w=0.0):
    """Reverse pass of the stochastic duration predictor.  At inference
    the first ConvFlow is left out, as in the exported graphs."""
    k, filt = dims["dp_kernel_size"], dims["dp_filter_channels"]
    h = conv(x, p["pre"])
    if g is not None and "cond" in p:
        h = h + conv(g, p["cond"])
    h = conv(dds_conv(h, mask, p["convs"], k), p["proj"]) * mask
    z = jnp.zeros(x.shape[:2] + (2,), jnp.float32)
    if eps is not None:
        z = eps * noise_w * mask
    bins = dims["dp_num_bins"]
    for flow in reversed(p["flows"][1:]):
        z = z[..., ::-1]
        z0, z1 = z[..., :1], z[..., 1]
        u = conv(dds_conv(conv(z0, flow["pre"]), mask, flow["convs"], k,
                          g=h), flow["proj"]) * mask
        z1 = spline_inverse(z1, u[..., :bins] / math.sqrt(filt),
                            u[..., bins:2 * bins] / math.sqrt(filt),
                            u[..., 2 * bins:], dims["dp_tail_bound"])
        z = jnp.concatenate([z0, z1[..., None] * mask], -1)
    z = z[..., ::-1]
    z = (z - p["affine"]["m"]) * jnp.exp(-p["affine"]["logs"]) * mask
    return z[..., :1]


def encode(params, dims, ids, lengths, sid=None, length_scale=1.0,
           eps=None, noise_w=0.0):
    """ids ``[B, T]`` -> prior mean, prior log-scale ``[B, T, C]``,
    real-valued durations ``[B, T]`` (frames before the ceiling)."""
    mask = mask_of(lengths, ids.shape[1])
    g = None
    if sid is not None and "emb_g" in params:
        g = params["emb_g"][sid][:, None, :]
    x, m_p, logs_p = text_encoder(params["enc_p"], dims, ids, mask)
    logw = log_durations(params["dp"], dims, x, mask, g, eps, noise_w)
    w = (jnp.exp(logw) * mask * length_scale)[..., 0]
    return m_p, logs_p, w


# -- length regulation, flow, generator ------------------------------------

def regulate(durations, prior, frames: int):
    """Repeat each phoneme's prior over its frames."""
    end = jnp.cumsum(durations, axis=1)
    f = jnp.arange(frames)[None, None, :]
    path = ((f < end[..., None]) & (f >= (end - durations)[..., None]))
    return jnp.einsum("btf,btc->bfc", path.astype(jnp.float32), prior)


def wavenet(x, mask, p, dims, g=None):
    hidden = x.shape[-1]
    n = dims["flow_wn_layers"]
    out = jnp.zeros_like(x)
    g_all = conv(g, p["cond"]) if g is not None and "cond" in p else None
    for i in range(n):
        a = conv(x, p["in"][i])
        if g_all is not None:
            a = a + g_all[..., 2 * hidden * i:2 * hidden * (i + 1)]
        acts = jnp.tanh(a[..., :hidden]) * jax.nn.sigmoid(a[..., hidden:])
        rs = conv(acts, p["res_skip"][i])
        if i < n - 1:
            x = (x + rs[..., :hidden]) * mask
            out = out + rs[..., hidden:]
        else:
            out = out + rs
    return out * mask


def flow_reverse(p, dims, z, mask, g=None):
    half = dims["inter_channels"] // 2
    for layer in reversed(p["layers"]):
        z = z[..., ::-1]
        z0, z1 = z[..., :half], z[..., half:]
        hdn = wavenet(conv(z0, layer["pre"]) * mask, mask, layer["wn"], dims,
                      g)
        z1 = (z1 - conv(hdn, layer["post"]) * mask) * mask
        z = jnp.concatenate([z0, z1], -1)
    return z


def generator(p, dims, z, g=None):
    x = conv(z, p["conv_pre"])
    if g is not None and "cond" in p:
        x = x + conv(g, p["cond"])
    kernels = dims["resblock_kernel_sizes"]
    for i, rate in enumerate(dims["upsample_rates"]):
        x = conv_transpose(jax.nn.leaky_relu(x, LRELU), p["ups"][i], rate)
        total = 0.0
        for j in range(len(kernels)):
            block = p["resblocks"][i * len(kernels) + j]
            y = x
            for c1, c2, dil in zip(block["convs1"], block["convs2"],
                                   dims["resblock_dilation_sizes"][j]):
                r = conv(jax.nn.leaky_relu(y, LRELU), c1, dilation=dil)
                r = conv(jax.nn.leaky_relu(r, LRELU), c2)
                y = y + r
            total = total + y
        x = total / len(kernels)
    x = conv(jax.nn.leaky_relu(x, LRELU), p["conv_post"])
    return jnp.tanh(x)[..., 0]


def synthesize(params, dims, m_p, logs_p, durations, frames: int, sid=None,
               eps=None, noise_scale=0.0):
    """Waveform ``[B, frames * hop]`` for given integer durations
    ``[B, T]``, and each row's length in frames."""
    durations = durations.astype(jnp.float32)
    y_len = jnp.clip(jnp.sum(durations, 1), 1, frames).astype(jnp.int32)
    y_mask = mask_of(y_len, frames)
    g = None
    if sid is not None and "emb_g" in params:
        g = params["emb_g"][sid][:, None, :]
    z_p = regulate(durations, m_p, frames)
    if eps is not None:
        z_p = z_p + eps * jnp.exp(regulate(durations, logs_p, frames)) \
            * noise_scale
    z = flow_reverse(params["flow"], dims, z_p, y_mask, g) * y_mask
    return generator(params["dec"], dims, z, g), y_len


def hop_length(dims) -> int:
    return math.prod(dims["upsample_rates"])
