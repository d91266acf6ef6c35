"""Plain reference of the GigaChat-3.5 backbone's forward pass
(``model_type: gigachat3_5``;
https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the whole sequence of one row
at once, no cache, no batching, no kernels; the linear layers as the
recurrence itself, position by position (no chunked form); latent attention
in its per-head form alone (nothing is absorbed into the queries); every
held expert is computed for every token and weighted (zero where it was not
chosen).  Nothing is imported from the program.  The caller hands the
weights in, one layer at a time.

Every norm is ``N_w(x) = x / sqrt(mean(x^2) + rms_norm_eps) *
layernorm_gating_weight * sigmoid(w)`` (``norm_type:
ZeroCenteredGatedNorm``: the gain is 1 at ``w = 0``).  Every layer, on the
residual ``h`` (``layernorm_type: pre_post``, no bias anywhere)::

    a = mixer(N(h; input_norm));   h = h + N(a; post_attn_norm)
    m = ffn(N(h; pre_mlp_norm));   h = h + N(m; post_mlp_norm)

- ``mixer``, every layer not in ``full_attention_layers`` (gated DeltaNet),
  ``x = N(h)``: ``[q | k | v] = x wqkv`` (``key heads x d_k | key heads x
  d_k | value heads x d_v``), ``z = x wz``, ``b = x wb``, ``a = x wa``; a
  causal depthwise convolution of ``linear_conv_kernel_dim`` taps over ``[q
  | k | v]`` (no bias), SiLU; ``q``, ``k`` divided a head by ``sqrt(sum x^2
  + 1e-6)``, ``q`` times ``d_k^-1/2``, key head ``j // (value heads / key
  heads)`` for value head ``j``; ``beta = sigmoid(b)``, ``alpha = exp(-exp(
  A_log) softplus(a + dt_bias))``; per value head, from ``S_0 = 0``::

      S_t = alpha_t S_{t-1} + k_t (x) beta_t (v_t - alpha_t S_{t-1}^T k_t)
      o_t = S_t^T q_t

  ``y = o / sqrt(mean(o^2; a head) + linear_attn_o_norm_eps) * (1 + o_norm)
  * linear_sigmoid_gate_scale * sigmoid(z)``; out ``y wout``.
- ``mixer``, a layer of ``full_attention_layers`` (MLA): ``c_q = N(x wq_a;
  q_norm)``; ``q = c_q wq_b``, per head ``[q_nope | q_rope]``; ``[c_kv |
  k_r] = x wkv_a``, ``c_kv = N(c_kv; kv_norm)``; rotary by halves at YaRN's
  paces (``rope_scaling``: ``theta^(-2i/d)`` where a rotation makes
  ``beta_fast`` turns or more in ``original_max_position_embeddings``, that
  over ``factor`` where it makes ``beta_slow`` or fewer, a linear ramp
  between; ``cos`` and ``sin`` unscaled since ``mscale = mscale_all_dim``)
  on every head's ``q_rope`` and the one ``k_r``; ``[k_nope_h | v_h] = c_kv
  wkv_b``; scores ``(q_nope_h . k_nope_h + q_rope_h . k_r) * (d_nope +
  d_rope)^-1/2 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``
  (``use_mla_scaling_factor``), causal, softmax; ``o = (concat_h(sum p
  v_h) * sigmoid(x wg)) wo`` (``gated_attention``).
- ``ffn``: SwiGLU ``(silu(min(u w1, L)) * clip(u w3, -L, L)) w2`` (``L =
  swiglu_limit``) of width ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them the expert layer: ``s =
  sigmoid(u router)``; the experts chosen are the ``num_experts_per_tok``
  largest of ``s + e_score_correction_bias`` (one group); their weights are
  their ``s`` divided by their sum + 1e-20 (``norm_topk_prob``), times
  ``routed_scaling_factor``; an expert is such a SwiGLU of width
  ``moe_intermediate_size``; a shared expert of the same form, with no
  gate of its own, is added for every token.
- a final norm ``N``, then the head, a matrix of its own.

Departures from the published graph: the residual stream is float32; rotary
by halves (the published code interleaves: with drawn weights a permutation
of ``wq_b``'s and ``wkv_a``'s columns); ``1e-20`` stands in the
normalisation where the program's shared router has ``1e-6``; the two
multi-token prediction modules are not run; ``held = (first, count)`` gives
the share of an expert layer that one chip of an expert-parallel deployment
computes (routing over all experts, the sum over the chosen experts it
holds, the shared expert whole), and the head is over whatever rows of the
vocabulary the caller hands in.

For the comparison's controls, ``faults`` (a dict, every key optional)
plants one fault each: ``round_to`` rounds what the configuration states as
float32 (the residual stream, every norm's result, every product's result,
router scores, attention scores, the softmax, and the delta-rule state
after every position) to another type; ``round_state`` the state alone;
``delta: False`` writes ``k (x) beta v`` without reading the state first
(``- alpha S^T k`` left out); ``decay: False`` holds ``alpha`` at 1;
``attn_gate: False`` leaves ``sigmoid(x wg)`` out; ``plain_norm`` takes a
norm's weight ``w`` itself for its gain; ``post_norm: False`` adds the
mixer's result to the residual without its norm; ``clamp: False`` runs
every SwiGLU unclamped.  ``start`` (an argument of :func:`layer`) is the
state and convolution columns a linear layer starts from in zero's place (a
slot whose last row's state was left).  ``forced`` ``[T, k]`` makes an
expert layer compute the experts it is handed instead of those it would
choose (the weights are still its own scores of them, and its own choice
is still what it returns).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"


def _same(a):
    return a


def norm(x, w, cfg: dict, faults: dict = None):
    """The zero-centred gated norm."""
    gain = w if (faults or {}).get("plain_norm") else float(
        cfg["layernorm_gating_weight"]) * jax.nn.sigmoid(w)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                         + float(cfg["rms_norm_eps"])) * gain


def yarn_paces(cfg: dict):
    """The rotary's paces ``[d_rope / 2]`` under ``rope_scaling``."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    rule = cfg["rope_scaling"]
    factor, original = float(rule["factor"]), int(
        rule["original_max_position_embeddings"])
    base = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def dim_of(turns: float) -> float:
        return d * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(float(rule["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rule["beta_slow"]))), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def rope(x, positions, cfg: dict):
    """``x`` ``[T, heads, d]`` at ``positions`` ``[T]``, by halves."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * yarn_paces(cfg)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def softmax_scale(cfg: dict) -> float:
    scale = float(int(cfg["qk_nope_head_dim"])
                  + int(cfg["qk_rope_head_dim"])) ** -0.5
    if cfg.get("use_mla_scaling_factor"):
        rule = cfg["rope_scaling"]
        scale *= (0.1 * float(rule["mscale_all_dim"])
                  * math.log(float(rule["factor"])) + 1.0) ** 2
    return scale


def attention(x, p, cfg: dict, faults: dict = None):
    """Gated latent attention over one row ``[T, H]``, per head."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    t = x.shape[0]
    heads, c = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, rot, v_dim = (int(cfg["qk_nope_head_dim"]),
                        int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    pos = jnp.arange(t)
    c_q = rnd(norm(rnd(x @ p["wq_a"]), p["q_norm"], cfg, faults))
    q = rnd(c_q @ p["wq_b"]).reshape(t, heads, nope + rot)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, cfg)
    down = rnd(x @ p["wkv_a"])
    c_kv = rnd(norm(down[:, :c], p["kv_norm"], cfg, faults))
    k_r = rope(down[:, None, c:], pos, cfg)[:, 0]
    kv = rnd(c_kv @ p["wkv_b"]).reshape(t, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) * softmax_scale(cfg)
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = rnd(jax.nn.softmax(jnp.where(causal, rnd(scores), -jnp.inf),
                               axis=-1))
    out = rnd(jnp.einsum("hqk,khd->qhd", probs, v)).reshape(t, heads * v_dim)
    if faults.get("attn_gate", True):
        out = out * jax.nn.sigmoid(rnd(x @ p["wg"]))
    return rnd(out @ p["wo"])


def delta_net(x, p, cfg: dict, faults: dict = None, start=None, length=None):
    """One gated DeltaNet layer over one row ``[T, H]``, the recurrence
    position by position.  ``start``: the state ``[value heads, d_k, d_v]``
    and the convolution's columns ``[taps - 1, C]`` the row starts from
    (zero where ``None``).  Also what the row leaves, as such a pair: after
    ``length`` positions where that is given (the state alone; the columns
    are the sequence's last), else after all."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    round_state = faults.get("round_state") or faults.get("round_to") or _same
    t = x.shape[0]
    kh, vh = int(cfg["linear_num_key_heads"]), int(
        cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(
        cfg["linear_value_head_dim"])
    taps = int(cfg["linear_conv_kernel_dim"])
    qkv, z = rnd(x @ p["wqkv"]), rnd(x @ p["wz"])
    b, a = rnd(x @ p["wb"]), rnd(x @ p["wa"])
    before = jnp.zeros((taps - 1, qkv.shape[1])) if start is None \
        else start[1]
    padded = jnp.concatenate([before, qkv], 0)
    columns = padded[t:]
    qkv = jax.nn.silu(sum(padded[j:j + t] * p["conv_w"][j]
                          for j in range(taps)))

    def unit(v):
        return v / jnp.sqrt(jnp.sum(v * v, -1, keepdims=True) + 1e-6)

    q = unit(qkv[:, :kh * dk].reshape(t, kh, dk)) * dk ** -0.5
    k = unit(qkv[:, kh * dk:2 * kh * dk].reshape(t, kh, dk))
    v = qkv[:, 2 * kh * dk:].reshape(t, vh, dv)
    q, k = (jnp.repeat(m, vh // kh, axis=1) for m in (q, k))
    beta = jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))
    if not faults.get("decay", True):
        alpha = jnp.ones_like(alpha)
    if length is not None:
        real = (jnp.arange(t) < length)[:, None]
        beta, alpha = jnp.where(real, beta, 0.0), jnp.where(real, alpha, 1.0)

    def one(state, at):
        q_t, k_t, v_t, beta_t, alpha_t = at
        state = alpha_t[:, None, None] * state
        if faults.get("delta", True):
            v_t = v_t - jnp.sum(state * k_t[:, :, None], 1)
        state = round_state(state + k_t[:, :, None]
                            * (beta_t[:, None] * v_t)[:, None, :])
        return state, jnp.sum(state * q_t[:, :, None], 1)

    first = jnp.zeros((vh, dk, dv)) if start is None else start[0]
    last, o = lax.scan(one, first, (q, k, v, beta, alpha))
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                      + float(cfg["linear_attn_o_norm_eps"])) \
        * (1.0 + p["o_norm"])
    gate = float(cfg["linear_sigmoid_gate_scale"]) * jax.nn.sigmoid(z)
    return rnd(rnd(o.reshape(t, vh * dv) * gate) @ p["wout"]), (last, columns)


def swiglu(u, w1, w3, w2, cfg: dict, faults: dict = None):
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    gate, up = rnd(u @ w1), rnd(u @ w3)
    if faults.get("clamp", True):
        limit = float(cfg["swiglu_limit"])
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return rnd(rnd(jax.nn.silu(gate) * up) @ w2)


def route_weights(scores, taken, cfg: dict):
    """The weights ``[T, k]`` of the experts ``taken``: their unbiased
    scores, normalised, scaled."""
    weights = jnp.take_along_axis(scores, taken, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * float(cfg["routed_scaling_factor"])


def route(u, p, cfg: dict, rnd=_same):
    """``(chosen [T, k], weights [T, k], scores [T, E])``."""
    scores = rnd(jax.nn.sigmoid(u @ p["router"]))
    _, chosen = lax.top_k(scores + p["e_score_correction_bias"],
                          int(cfg["num_experts_per_tok"]))
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
        return gate[:, None] * swiglu(u, w1, w3, w2, cfg, faults)

    out = jnp.sum(lax.map(one, (jnp.arange(count), p["w1"], p["w3"],
                                p["w2"])), 0)
    return out + swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                        cfg, faults), chosen


def is_full(i: int, cfg: dict) -> bool:
    return i in [int(j) for j in cfg["full_attention_layers"]]


def kind_of(i: int, cfg: dict) -> tuple:
    """What :func:`layer` reads of ``i``: whether the layer's mixer is the
    full one and whether its feed-forward is dense (layers of one kind are
    one computation)."""
    return is_full(i, cfg), i < int(cfg["first_k_dense_replace"])


def layer(h, p, i: int, cfg: dict, held=None, faults: dict = None,
          forced=None, start=None, length=None):
    """Layer ``i`` over one row ``[T, H]``; also the experts chosen
    (``None`` in a dense layer) and the state the row leaves (``None`` in a
    full layer)."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    x = rnd(norm(h, p["input_norm"], cfg, faults))
    state = None
    if is_full(i, cfg):
        a = attention(x, p["mixer"], cfg, faults)
    else:
        a, state = delta_net(x, p["mixer"], cfg, faults, start, length)
    if faults.get("post_norm", True):
        a = rnd(norm(a, p["post_attn_norm"], cfg, faults))
    h = rnd(h + a)
    u = rnd(norm(h, p["pre_mlp_norm"], cfg, faults))
    chosen = None
    if i < int(cfg["first_k_dense_replace"]):
        m = swiglu(u, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"], cfg,
                   faults)
    else:
        m, chosen = experts(u, p["ffn"], cfg, held, faults, forced)
    return (rnd(h + rnd(norm(m, p["post_mlp_norm"], cfg, faults))), chosen,
            state)


def left(h, p, cfg: dict):
    """What a row ``[T, H]`` leaves in the linear layer ``p``: its state and
    the convolution's last columns."""
    return delta_net(norm(h, p["input_norm"], cfg), p["mixer"], cfg)[1]


def head(h, head_w, norm_f, cfg: dict):
    return norm(h, norm_f, cfg) @ head_w.T


def forward(tokens, cfg: dict, embed, head_w, norm_f, layer_weights,
            held=None, faults: dict = None):
    """Logits ``[T, V]`` of one row of token ids ``[T]``, the experts
    chosen in each expert layer ``[T, expert layers, k]`` and the state the
    row leaves in each linear layer.  ``layer_weights(i)`` gives layer
    ``i``'s weights (float32)."""
    fns: dict = {}
    with jax.default_matmul_precision(HIGHEST):
        h = embed[tokens]
        routes, states = [], []
        for i in range(int(cfg["num_hidden_layers"])):
            kind = kind_of(i, cfg)
            if kind not in fns:
                fns[kind] = jax.jit(lambda h, p, i=i: layer(
                    h, p, i, cfg, held, faults))
            h, chosen, state = fns[kind](h, layer_weights(i))
            if chosen is not None:
                routes.append(chosen)
            if state is not None:
                states.append(state[0])
        return head(h, head_w, norm_f, cfg), jnp.stack(routes, 1), states
