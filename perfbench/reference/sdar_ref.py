"""Plain reference of the SDAR-MoE backbone (``model_type: sdar_moe``;
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json): the
forward pass, and the family's published generation loop.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the whole sequence of one row
at once, no cache, no batching, no kernels, every expert computed for every
token and weighted (zero where it was not chosen).  Nothing is imported from
the program.  The caller hands the weights in, one layer at a time
(``layer_weights(i)``), so that a float32 expert layer (2.4 GB at the
published widths) lies on the device beside nothing else.

Every layer (all alike) is ``h += attn(rms(h, in_norm)); h += moe(rms(h,
post_norm))``:

- ``attn``: ``q, k, v = u wq, u wk, u wv`` as ``num_attention_heads``,
  ``num_key_value_heads``, ``num_key_value_heads`` heads of ``head_dim``; an
  RMS norm over each head of q and of k, then RoPE (rotate-half over the
  whole head, ``rope_theta``); ``softmax(q k^T / sqrt(head_dim) + M) v``,
  each key head serving ``heads / kv heads`` query heads; ``wo``; no biases.
  **The mask** ``M`` (:func:`block_mask`): position ``i`` sees ``j`` iff
  ``j // B <= i // B``, whole inside a block of ``B``, causal between blocks.
- ``moe``: ``s = softmax(u @ router)`` over all experts; the
  ``num_experts_per_tok`` best; their weights ``s_e / sum_chosen s``
  (``norm_topk_prob``); each expert ``(silu(u w1) * (u w3)) w2``; no shared
  expert.
- a final RMS norm, then ``head`` (its own matrix, ``[V, H]``).

Generation (:func:`generate`; the published ``generate.py`` with
``remasking_strategy: low_confidence_static``): blocks of ``B``; the logits
at a position predict the token at that position; a block starts as mask
tokens (the first one holds the prompt's last ``n mod B`` ids); a denoising
pass runs prompt + committed blocks + the block whole, picks an id for every
masked position and unmasks the ``B / denoising_steps`` it is surest of
(the id's probability under the distribution it was chosen from).

Departures from the published description: ``held = (first, count)`` gives
the share of an expert layer that one chip of an expert-parallel deployment
computes; ``round_to`` rounds what the configuration states as float32 to
another type (the comparison's lower-precision control); ``forced`` ``[T,
k]`` makes an expert layer compute the experts it is handed instead of those
it would choose (weights: its own scores of them; what it returns: still its
own choice); :func:`attn` takes the positions and the mask as arguments
(default: ``0 .. T-1`` and ``M``), so that the comparison can run several
views of one block beside one clean sequence, each at the block's positions
and seeing what it would see alone (``sdar_check.py``), and can mask a block
causally for its ``causal_block`` control; :func:`generate` gives every
block ``denoising_steps`` denoising passes and then its commit pass (the
published loop commits a block as soon as it holds no mask token, which
only the first block of a prompt with ``n mod B > 0`` can do early; a pass
over a block without mask tokens changes nothing), and has no commit pass of
its own to make, because it keeps no cache; greedy only (temperature 0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
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


def block_mask(t: int, block: int):
    """``M`` as booleans ``[T, T]``: row ``i`` sees column ``j``."""
    at = jnp.arange(t) // block
    return at[:, None] >= at[None, :]


def attn(u, p, cfg: dict, visible, positions, round_to=None):
    t = u.shape[0]
    heads, kv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    q = rope(rms_norm((u @ p["wq"]).reshape(t, heads, d), p["q_norm"], eps),
             positions, theta)
    k = rope(rms_norm((u @ p["wk"]).reshape(t, kv, d), p["k_norm"], eps),
             positions, theta)
    v = (u @ p["wv"]).reshape(t, kv, d)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(float(d))
    probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
    if round_to is not None:
        probs = round_to(probs)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * d) \
        @ p["wo"]


def swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def route_weights(scores, taken, cfg: dict):
    weights = jnp.take_along_axis(scores, taken, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights


def route(u, p, cfg: dict, round_to=None):
    """``(chosen [T, k], weights [T, k], scores [T, E])``."""
    scores = jax.nn.softmax(u @ p["router"], -1)
    if round_to is not None:
        scores = round_to(scores)
    _, chosen = lax.top_k(scores, int(cfg["num_experts_per_tok"]))
    return chosen, route_weights(scores, chosen, cfg), scores


def moe(u, p, cfg: dict, held=None, round_to=None, forced=None):
    """The expert layer's output ``[T, H]`` and the experts chosen ``[T,
    k]``.  ``held = (first, count)``: only the chosen experts among ``first
    .. first + count - 1`` add to the result (``p["w1"]`` holds those
    ``count`` experts); ``None`` is the whole layer.  With ``forced`` ``[T,
    k]`` those experts are computed in the chosen ones' place."""
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


def layer(h, p, cfg: dict, visible, positions, held=None, round_to=None,
          forced=None):
    """One layer over one row ``[T, H]``; also the experts chosen."""
    eps = float(cfg["rms_norm_eps"])
    rnd = round_to if round_to is not None else (lambda a: a)
    h = rnd(h + attn(rms_norm(h, p["in_norm"], eps), p["attn"], cfg, visible,
                     positions, round_to))
    out, chosen = moe(rms_norm(h, p["post_norm"], eps), p["moe"], cfg, held,
                      round_to, forced)
    return rnd(h + out), chosen


def head(h, head_w, norm_f, cfg: dict):
    return rms_norm(h, norm_f, float(cfg["rms_norm_eps"])) @ head_w.T


def forward(tokens, cfg: dict, block: int, embed, head_w, norm_f,
            layer_weights, round_to=None):
    """Logits ``[T, V]`` of one row of token ids ``[T]`` under ``M`` and
    the experts chosen in each layer ``[T, layers, k]``.
    ``layer_weights(i)`` gives layer ``i``'s weights (float32)."""
    t = tokens.shape[0]
    visible, positions = block_mask(t, block), jnp.arange(t)
    with jax.default_matmul_precision(HIGHEST):
        step = jax.jit(lambda h, p: layer(h, p, cfg, visible, positions,
                                          None, round_to))
        h = embed[tokens]
        routes = []
        for i in range(int(cfg["num_hidden_layers"])):
            h, chosen = step(h, layer_weights(i))
            routes.append(chosen)
        return head(h, head_w, norm_f, cfg), jnp.stack(routes, 1)


def transfers(block: int, steps: int) -> list:
    base, more = divmod(block, steps)
    return [base + (k < more) for k in range(steps)]


def generate(ids, budget: int, logits_of, *, block: int, steps: int,
             mask_id: int, first_id: int, stop_id: int) -> tuple:
    """The published loop, greedy: ``budget`` units after the prompt
    ``ids``.  ``logits_of(tokens)`` is the whole forward pass.  Returns the
    units and, for every pass, ``(the sequence as it went in, its logits at
    the block)``."""
    seq = list(ids)
    start, end = len(seq) // block * block, len(seq) + budget
    passes = []
    while start < end:
        seq += [mask_id] * (start + block - len(seq))
        for count in transfers(block, steps) + [0]:
            logits = np.asarray(logits_of(np.asarray(seq, np.int32)))[start:]
            passes.append((list(seq), logits))
            allowed = logits[:, first_id:].copy()
            allowed[:, [stop_id - first_id, mask_id - first_id]] = -np.inf
            best = allowed.argmax(-1)
            # the best id's log-probability over the ids a row may choose
            log_p = -np.log(np.sum(np.exp(
                allowed - allowed.max(-1, keepdims=True)), -1))
            masked = np.asarray(seq[start:]) == mask_id
            surest = np.argsort(np.where(masked, -log_p, np.inf),
                                kind="stable")[:count]
            for j in surest[masked[surest]]:
                seq[start + j] = first_id + int(best[j])
        start += block
    return np.asarray(seq[len(ids):end], np.int32), passes
