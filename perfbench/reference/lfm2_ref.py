"""Plain reference of the LFM2-MoE backbone's forward pass (``model_type:
lfm2_moe``; https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the whole sequence of one row
at once, no cache, no batching, no kernels, every expert computed for every
token and weighted (zero where it was not chosen).  Nothing is imported from
the program.  The caller hands the weights in, one layer at a time
(``layer_weights(i)``), so that a float32 expert layer (2.4 GB at the
published widths) lies on the device beside nothing else.

Every layer is ``h += op(rms(h)); h += ffn(rms(h))``.

- ``op`` of a ``conv`` layer: ``B, C, x = split3(u @ in_proj)``;
  ``y = C * causal_depthwise_conv1d(B * x, kernel conv_L_cache, no bias)``;
  ``op = y @ out_proj``.
- ``op`` of a ``full_attention`` layer: grouped-query attention, an RMS norm
  over each head of q and of k before RoPE (rotate-half, ``rope_theta``),
  causal softmax, no biases.
- ``ffn`` of the first ``num_dense_layers`` layers: ``(silu(u w1) * (u w3)) w2``.
- ``ffn`` of the others: ``s = sigmoid(u @ router)``; the experts chosen are
  ``top_k(s + expert_bias)``; their weights are the *unbiased* ``s`` of the
  chosen, divided by their sum + 1e-6 (``norm_topk_prob``), times
  ``routed_scaling_factor``; each expert is a SwiGLU; no shared expert.
- a final RMS norm, then the head, tied to the embedding.

Departures from the published graph: ``held = (first, count)`` gives the
share of an expert layer that one chip of an expert-parallel deployment
computes (routing over all experts, the sum over the chosen experts it
holds); ``round_to`` rounds what the configuration states as float32 (the
residual stream, router scores, softmax) to another type, which is how the
comparison's lower-precision control is computed; ``forced`` ``[T, k]`` makes
an expert layer compute the experts it is handed instead of those it would
choose (the weights are still its own scores of them, and its own choice is
still what it returns): the comparison walks the routes the served path
took, so that a near-tie the two resolve differently does not stand between
their arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"


def rms_norm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta: float):
    """``x`` ``[T, heads, d]``; rotate-half RoPE at ``positions`` ``[T]``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def conv_op(u, p, cfg: dict):
    k = int(cfg["conv_L_cache"])
    b, c, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
    bx = b * x
    padded = jnp.pad(bx, ((k - 1, 0), (0, 0)))
    t = u.shape[0]
    conv = sum(padded[j:j + t] * p["conv_w"][j] for j in range(k))
    return (c * conv) @ p["out_proj"]


def attn_op(u, p, cfg: dict, round_to=None):
    t = u.shape[0]
    heads, kv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    d = int(cfg["hidden_size"]) // heads
    eps, theta = float(cfg["norm_eps"]), float(
        cfg["rope_parameters"]["rope_theta"])
    pos = jnp.arange(t)
    q = rope(rms_norm((u @ p["wq"]).reshape(t, heads, d), p["q_norm"], eps),
             pos, theta)
    k = rope(rms_norm((u @ p["wk"]).reshape(t, kv, d), p["k_norm"], eps),
             pos, theta)
    v = (u @ p["wv"]).reshape(t, kv, d)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d))
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    if round_to is not None:
        probs = round_to(probs)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * d) \
        @ p["wo"]


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def dense_ffn(u, p):
    return swiglu(u, p["w1"], p["w3"], p["w2"])


def route_weights(scores, taken, cfg: dict):
    """The weights ``[T, k]`` of the experts ``taken``: their unbiased
    scores, normalised."""
    weights = jnp.take_along_axis(scores, taken, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return weights * float(cfg["routed_scaling_factor"])


def route(u, p, cfg: dict, round_to=None):
    """``(chosen [T, k], weights [T, k], scores [T, E])``."""
    scores = jax.nn.sigmoid(u @ p["router"])
    if round_to is not None:
        scores = round_to(scores)
    pick = scores + p["expert_bias"] if cfg["use_expert_bias"] else scores
    _, chosen = lax.top_k(pick, int(cfg["num_experts_per_tok"]))
    return chosen, route_weights(scores, chosen, cfg), scores


def moe_ffn(u, p, cfg: dict, held=None, round_to=None, forced=None):
    """The expert layer's output ``[T, H]`` and the experts chosen
    ``[T, k]``.  ``held = (first, count)``: only the chosen experts among
    ``first .. first + count - 1`` add to the result (``p["w1"]`` holds
    those ``count`` experts); ``None`` is the whole layer.  With ``forced``
    ``[T, k]`` those experts are computed in the chosen ones' place."""
    chosen, weights, scores = route(u, p, cfg, round_to)
    taken = chosen
    if forced is not None:
        taken, weights = forced, route_weights(scores, forced, cfg)
    first, count = held if held is not None else (0, p["w1"].shape[0])

    def one(args):
        e, w1, w3, w2 = args
        gate = jnp.sum(jnp.where(taken == first + e, weights, 0.0), -1)
        return gate[:, None] * swiglu(u, w1, w3, w2)

    parts = lax.map(one, (jnp.arange(count), p["w1"], p["w3"], p["w2"]))
    return jnp.sum(parts, 0), chosen


def layer(h, p, kind: str, dense: bool, cfg: dict, held=None, round_to=None,
          forced=None):
    """One layer over one row ``[T, H]``; also the experts chosen
    (``None`` for a dense layer)."""
    eps = float(cfg["norm_eps"])
    rnd = round_to if round_to is not None else (lambda a: a)
    u = rms_norm(h, p["op_norm"], eps)
    op = conv_op(u, p["op"], cfg) if kind == "conv" else attn_op(
        u, p["op"], cfg, round_to)
    h = rnd(h + op)
    u = rms_norm(h, p["ffn_norm"], eps)
    if dense:
        return rnd(h + dense_ffn(u, p["ffn"])), None
    out, chosen = moe_ffn(u, p["ffn"], cfg, held, round_to, forced)
    return rnd(h + out), chosen


def head(h, embed, norm_f, cfg: dict):
    return rms_norm(h, norm_f, float(cfg["norm_eps"])) @ embed.T


def forward(tokens, cfg: dict, embed, norm_f, layer_weights, round_to=None):
    """Logits ``[T, V]`` of one row of token ids ``[T]`` and the experts
    chosen in each expert layer ``[T, expert layers, k]``.
    ``layer_weights(i)`` gives layer ``i``'s weights (float32)."""
    fns: dict = {}
    with jax.default_matmul_precision(HIGHEST):
        h = embed[tokens]
        routes = []
        for i, kind in enumerate(cfg["layer_types"]):
            dense = i < int(cfg["num_dense_layers"])
            if (kind, dense) not in fns:
                fns[kind, dense] = jax.jit(
                    lambda h, p, kind=kind, dense=dense: layer(
                        h, p, kind, dense, cfg, None, round_to))
            h, chosen = fns[kind, dense](h, layer_weights(i))
            if chosen is not None:
                routes.append(chosen)
        return head(h, embed, norm_f, cfg), jnp.stack(routes, 1)
