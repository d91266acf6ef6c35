"""Plain reference of the openPangu-Ultra-MoE backbone's forward pass
(``model_type: pangu_ultra_moe``;
https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the whole sequence of one row
at once, no cache, no batching, no kernels; latent attention in its
per-head form alone (every head's keys and values are made of every
position's ``c_kv``; nothing is absorbed into the queries); every held
expert is computed for every token and weighted (zero where it was not
chosen).  Nothing is imported from the program.  The caller hands the
weights in, one layer at a time.

Every layer, on the residual ``h`` (``sandwich_norm: true``, eps
``rms_norm_eps``, no bias anywhere)::

    a = attn(rms(h; input_norm));   h = h + rms(a; post_attn_norm)
    m = ffn(rms(h; pre_mlp_norm));  h = h + rms(m; post_mlp_norm)

- ``attn`` (MLA), ``u = rms(h)``: ``c_q = rms(u wq_a; q_norm)``
  (``q_lora_rank``); ``q = c_q wq_b``, per head ``[q_nope | q_rope]``
  (``qk_nope_head_dim | qk_rope_head_dim``); ``[c_kv | k_r] = u wkv_a``
  (``kv_lora_rank | qk_rope_head_dim``), ``c_kv = rms(c_kv; kv_norm)``;
  rotary (``rope_theta``, no scaling, by halves) on every head's ``q_rope``
  and on the one ``k_r`` every head shares; ``[k_nope_h | v_h] = c_kv
  wkv_b`` per head (``qk_nope_head_dim | v_head_dim``); scores ``(q_nope_h .
  k_nope_h + q_rope_h . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``,
  causal, softmax; ``o = concat_h(sum p v_h) wo``.
- ``ffn``: SwiGLU ``(silu(u w1) * (u w3)) w2`` of width ``intermediate_size``
  in the first ``first_k_dense_replace`` layers; after them the expert
  layer: ``s = sigmoid(u router)``; the experts chosen are the
  ``num_experts_per_tok`` largest of ``s`` (no group limit, no correction
  bias); their weights are their ``s`` divided by their sum + 1e-20
  (``norm_topk_prob``), times ``routed_scaling_factor``; an expert is a
  SwiGLU of width ``moe_intermediate_size``; a shared expert of the same
  form is added for every token.
- a final RMS norm, then the head, a matrix of its own.

Departures from the published graph: the residual stream is float32;
rotary by halves (the published code interleaves: with drawn weights a
permutation of ``wq_b``'s and ``wkv_a``'s columns); ``1e-20`` stands in the
normalisation where the program's shared router (``lfm2.route``) has
``1e-6``; the multi-token prediction module is not run (the published
forward pass does not run it either); ``held = (first, count)`` gives the
share of an expert layer that one chip of an expert-parallel deployment
computes (routing over all experts, the sum over the chosen experts it
holds, the shared expert whole), and the head is over whatever rows of the
vocabulary the caller hands in.

For the comparison's controls, ``faults`` (a dict, every key optional)
plants one fault each: ``round_to`` rounds what the configuration states as
float32 (the residual stream, every norm's result, ``c_kv``'s among them,
every product's result, router scores, attention scores and the softmax)
to another type; ``rope_on_latent: False`` leaves the shared key's 64
dimensions unrotated (the queries' are rotated still); ``post_norm: False``
adds the attention's result to the residual without its norm; ``stale``
``(rows, count)`` puts the ``[c_kv | k_r]`` rows another row left in the
first ``count`` positions in this row's place (a slot whose prompt rows
were not written).  ``forced`` ``[T, k]`` makes an expert layer compute the
experts it is handed instead of those it would choose (the weights are
still its own scores of them, and its own choice is still what it
returns).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"


def rms_norm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def eps_of(cfg: dict) -> float:
    return float(cfg["rms_norm_eps"])


def _same(a):
    return a


def rope(x, positions, theta: float):
    """``x`` ``[T, heads, d]`` at ``positions`` ``[T]``, by halves."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def latent_rows(u, p, cfg: dict, faults: dict = None):
    """What a cache would hold of ``u`` ``[T, H]``: ``[c_kv | k_r]`` ``[T,
    c + rope]`` after its norm and rotary."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    c = int(cfg["kv_lora_rank"])
    down = rnd(u @ p["wkv_a"])
    c_kv = rnd(rms_norm(down[:, :c], p["kv_norm"], eps_of(cfg)))
    k_r = down[:, None, c:]
    if faults.get("rope_on_latent", True):
        k_r = rope(k_r, jnp.arange(u.shape[0]), float(cfg["rope_theta"]))
    return jnp.concatenate([c_kv, k_r[:, 0]], -1)


def attention(u, p, cfg: dict, faults: dict = None):
    """Latent attention over one row ``[T, H]``, per head."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    t = u.shape[0]
    heads, c = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, rot, v_dim = (int(cfg["qk_nope_head_dim"]),
                        int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    pos = jnp.arange(t)
    c_q = rnd(rms_norm(rnd(u @ p["wq_a"]), p["q_norm"], eps_of(cfg)))
    q = rnd(c_q @ p["wq_b"]).reshape(t, heads, nope + rot)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos,
                                         float(cfg["rope_theta"]))
    rows = latent_rows(u, p, cfg, faults)
    if faults.get("stale") is not None:
        other, count = faults["stale"]
        rows = jnp.where((pos < count)[:, None], other, rows)
    c_kv, k_r = rows[:, :c], rows[:, c:]
    kv = rnd(c_kv @ p["wkv_b"]).reshape(t, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) \
        * float(nope + rot) ** -0.5
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = rnd(jax.nn.softmax(jnp.where(causal, rnd(scores), -jnp.inf),
                               axis=-1))
    out = rnd(jnp.einsum("hqk,khd->qhd", probs, v)).reshape(t, heads * v_dim)
    return rnd(out @ p["wo"])


def swiglu(u, w1, w3, w2, rnd=_same):
    return rnd(rnd(jax.nn.silu(rnd(u @ w1)) * rnd(u @ w3)) @ w2)


def route_weights(scores, taken, cfg: dict):
    """The weights ``[T, k]`` of the experts ``taken``: their scores,
    normalised, scaled."""
    weights = jnp.take_along_axis(scores, taken, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * float(cfg["routed_scaling_factor"])


def route(u, p, cfg: dict, rnd=_same):
    """``(chosen [T, k], weights [T, k], scores [T, E])``."""
    scores = rnd(jax.nn.sigmoid(u @ p["router"]))
    _, chosen = lax.top_k(scores, int(cfg["num_experts_per_tok"]))
    return chosen, route_weights(scores, chosen, cfg), scores


def experts(u, p, cfg: dict, held=None, faults: dict = None, forced=None):
    """The expert layer's output ``[T, H]`` and the experts chosen
    ``[T, k]``.  ``held = (first, count)``: only the chosen experts among
    ``first .. first + count - 1`` add to the result (``p["w1"]`` holds
    those ``count`` experts); ``None`` is the whole layer.  With ``forced``
    ``[T, k]`` those experts are computed in the chosen ones' place."""
    rnd = (faults or {}).get("round_to") or _same
    chosen, weights, scores = route(u, p, cfg, rnd)
    taken = chosen
    if forced is not None:
        taken, weights = forced, route_weights(scores, forced, cfg)
    first, count = held if held is not None else (0, p["w1"].shape[0])

    def one(args):
        e, w1, w3, w2 = args
        gate = jnp.sum(jnp.where(taken == first + e, weights, 0.0), -1)
        return gate[:, None] * swiglu(u, w1, w3, w2, rnd)

    out = jnp.sum(lax.map(one, (jnp.arange(count), p["w1"], p["w3"],
                                p["w2"])), 0)
    return out + swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                        rnd), chosen


def layer(h, p, dense: bool, cfg: dict, held=None, faults: dict = None,
          forced=None):
    """One layer over one row ``[T, H]``; also the experts chosen (``None``
    in a dense layer)."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    eps = eps_of(cfg)
    a = attention(rnd(rms_norm(h, p["input_norm"], eps)), p["attn"], cfg,
                  faults)
    if faults.get("post_norm", True):
        a = rnd(rms_norm(a, p["post_attn_norm"], eps))
    h = rnd(h + a)
    u = rnd(rms_norm(h, p["pre_mlp_norm"], eps))
    chosen = None
    if dense:
        m = swiglu(u, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"], rnd)
    else:
        m, chosen = experts(u, p["ffn"], cfg, held, faults, forced)
    return rnd(h + rnd(rms_norm(m, p["post_mlp_norm"], eps))), chosen


def left(h, p, cfg: dict):
    """The ``[c_kv | k_r]`` rows a row ``[T, H]`` leaves in layer ``p``."""
    return latent_rows(rms_norm(h, p["input_norm"], eps_of(cfg)), p["attn"],
                       cfg)


def head(h, head_w, norm_f, cfg: dict):
    return rms_norm(h, norm_f, eps_of(cfg)) @ head_w.T


def forward(tokens, cfg: dict, embed, head_w, norm_f, layer_weights,
            held=None, faults: dict = None):
    """Logits ``[T, V]`` of one row of token ids ``[T]`` and the experts
    chosen in each expert layer ``[T, expert layers, k]``.
    ``layer_weights(i)`` gives layer ``i``'s weights (float32)."""
    fns: dict = {}
    with jax.default_matmul_precision(HIGHEST):
        h = embed[tokens]
        routes = []
        for i in range(int(cfg["num_hidden_layers"])):
            dense = i < int(cfg["first_k_dense_replace"])
            if dense not in fns:
                fns[dense] = jax.jit(lambda h, p, dense=dense: layer(
                    h, p, dense, cfg, held, faults))
            h, chosen = fns[dense](h, layer_weights(i))
            if chosen is not None:
                routes.append(chosen)
        return head(h, head_w, norm_f, cfg), jnp.stack(routes, 1)
