"""Plain reference of the Laguna backbone's forward pass (``model_type:
laguna``; https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the whole sequence of one row
at once, no cache, no ring, no batching, no kernels; the window of a
sliding layer is a mask over the whole row; every held expert is computed
for every token and weighted (zero where it was not chosen).  Nothing is
imported from the program.  The caller hands the weights in, one layer at a
time.

Every layer, on the residual ``h`` (eps ``rms_norm_eps``, no bias
anywhere)::

    h = h + attn(rms(h; attn_norm));   h = h + ffn(rms(h; ffn_norm))

- ``attn``, ``u = rms(h)``; layer ``l``'s kind is ``layer_types[l]``, its
  query heads ``n_q = num_attention_heads_per_layer[l]`` over
  ``num_key_value_heads`` heads of ``head_dim`` (query head ``j`` reads
  key-value head ``j // (n_q / kv)``): ``q = u wq``, ``k = u wk``, ``v = u
  wv``, no norm on either.  Rotary by ``rope_parameters[kind]``, by halves,
  on the first ``partial_rotary_factor`` of a head's dimensions (the others
  pass): ``default`` at ``inv_freq_i = rope_theta ^ (-2i / dims)``; ``yarn``
  at ``inv_freq_i = (1 - r_i) base_i / factor + r_i base_i`` with ``r_i`` 1
  minus the linear ramp over ``i`` from ``low`` to ``high``, the dimensions
  at which a rotation makes ``beta_fast`` and ``beta_slow`` turns in
  ``original_max_position_embeddings`` positions (floored and ceiled), and
  ``cos`` and ``sin`` times ``attention_factor``.  Scores ``q k^T /
  sqrt(head_dim)``, causal; in a ``sliding_attention`` layer a query at
  ``i`` sees ``i - sliding_window < j <= i``.  The gate (``gating``): ``a =
  sigmoid(u wg)``, one number a query head, times the head's result;
  then ``wo``.
- ``ffn``: SwiGLU ``(silu(u w1) * (u w3)) w2`` of width ``intermediate_size``
  where ``mlp_layer_types[l]`` is ``dense``; else the expert layer: ``s =
  sigmoid(u router)``; the experts chosen are the ``num_experts_per_tok``
  largest of ``s`` (no groups, no bias); their weights are their ``s``
  divided by their sum + 1e-20, times ``moe_routed_scaling_factor``, on the
  experts' results; an expert is a SwiGLU of width
  ``moe_intermediate_size``; a shared expert of width
  ``shared_expert_intermediate_size`` is added for every token.
- a final RMS norm, then the head, a matrix of its own.

Departures from the published graph: the residual stream is float32;
``1e-20`` stands in the normalisation where the program's shared router
(``lfm2.route``) has ``1e-6``; ``held = (first, count)`` gives the share of
an expert layer that one chip of an expert-parallel deployment computes
(routing over all experts, the sum over the chosen experts it holds, the
shared expert whole).  The router's rule, the gate's form and the absence
of a norm on queries and keys are the configuration's ``assumed``.

For the comparison's controls, ``faults`` (a dict, every key optional)
plants one fault each: ``round_to`` rounds what the configuration states as
float32 (the residual stream, every norm's result, every product's result,
rotated queries and keys, the gate, router scores, attention scores and
the softmax) to another type; ``window: False`` lets a sliding layer see
every earlier position; ``full_rule`` names the kind whose rotary rule the
full layers take (``sliding_attention``: plain rotary on every dimension);
``yarn_factor: False`` leaves ``cos`` and ``sin`` unscaled; ``gate: False``
drops the gate; ``stale`` ``(keys, values, length)`` makes a sliding layer's
query at ``i < sliding_window`` also see, at every place of the ring past
``i``, what another row of ``length`` positions left there (a reused slot
whose old places are readable).  ``forced`` ``[T, k]`` makes an expert
layer compute the experts it is handed instead of those it would choose
(the weights are still its own scores of them, and its own choice is still
what it returns).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
FULL, SLIDING = "full_attention", "sliding_attention"


def rms_norm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def eps_of(cfg: dict) -> float:
    return float(cfg["rms_norm_eps"])


def _same(a):
    return a


def inv_freq(rule: dict, dims: int) -> list:
    """The paces of ``dims`` rotated dimensions under one kind's rule."""
    theta = float(rule["rope_theta"])
    base = [theta ** (-2.0 * i / dims) for i in range(dims // 2)]
    if rule.get("rope_type", "default") == "default":
        return base
    factor = float(rule["factor"])
    original = float(rule["original_max_position_embeddings"])

    def dimension(turns: float) -> float:
        return dims * math.log(original / (turns * 2.0 * math.pi)) / (
            2.0 * math.log(theta))

    low = max(math.floor(dimension(float(rule["beta_fast"]))), 0)
    high = min(math.ceil(dimension(float(rule["beta_slow"]))), dims - 1)
    out = []
    for i, b in enumerate(base):
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        r = 1.0 - ramp
        out.append((1.0 - r) * b / factor + r * b)
    return out


def rope(x, positions, rule: dict, yarn_factor: bool = True):
    """``x`` ``[T, heads, d]`` at ``positions`` ``[T]`` under one kind's
    rule, by halves on the first ``partial_rotary_factor`` of ``d``."""
    d = x.shape[-1]
    dims = int(d * float(rule.get("partial_rotary_factor", 1)))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq(rule, dims), jnp.float32)[None, :]
    scale = float(rule.get("attention_factor", 1.0)) if yarn_factor and \
        rule.get("rope_type") == "yarn" else 1.0
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None] * scale
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None] * scale
    turned, passed = x[..., :dims], x[..., dims:]
    x1, x2 = turned[..., :dims // 2], turned[..., dims // 2:]
    turned = turned * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, passed], -1)


def rule_of(kind: str, cfg: dict, faults: dict) -> dict:
    if kind == FULL and faults.get("full_rule"):
        kind = faults["full_rule"]
    return cfg["rope_parameters"][kind]


def keys_values(u, p, kind: str, cfg: dict, faults: dict = None):
    """What a cache would hold of ``u`` ``[T, H]`` in a layer of ``kind``:
    keys after their rotary and values, ``[T, kv, d]`` each."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    t = u.shape[0]
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    k = rnd(u @ p["wk"]).reshape(t, kv, d)
    v = rnd(u @ p["wv"]).reshape(t, kv, d)
    k = rnd(rope(k, jnp.arange(t), rule_of(kind, cfg, faults),
                 faults.get("yarn_factor", True)))
    return k, v


def ring_left(k, v, length, window: int):
    """What a ring of ``window`` places holds of a row of ``length``
    positions: at place ``j`` the row's latest position congruent to
    ``j``."""
    place = jnp.arange(window)
    at = jnp.clip(place + (length - 1 - place) // window * window, 0,
                  k.shape[0] - 1)
    return k[at], v[at]


def attention(u, p, kind: str, heads: int, cfg: dict, faults: dict = None):
    """One layer's attention over one row ``[T, H]``."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    t = u.shape[0]
    kv, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    window = int(cfg["sliding_window"])
    pos = jnp.arange(t)
    q = rnd(u @ p["wq"]).reshape(t, heads, d)
    q = rnd(rope(q, pos, rule_of(kind, cfg, faults),
                 faults.get("yarn_factor", True)))
    k, v = keys_values(u, p, kind, cfg, faults)
    seen = pos[:, None] >= pos[None, :]
    if kind == SLIDING and faults.get("window", True):
        seen &= pos[:, None] - pos[None, :] < window
    if kind == SLIDING and faults.get("stale") is not None:
        other_k, other_v, length = faults["stale"]
        old_k, old_v = ring_left(other_k, other_v, length, window)
        k, v = jnp.concatenate([k, old_k]), jnp.concatenate([v, old_v])
        place = jnp.arange(window)
        seen = jnp.concatenate(
            [seen, (place[None, :] > pos[:, None])
             & (pos[:, None] < window)], 1)
    q = q.reshape(t, kv, heads // kv, d)
    scores = jnp.einsum("qkgd,pkd->kgqp", q, k) / math.sqrt(d)
    probs = rnd(jax.nn.softmax(jnp.where(seen, rnd(scores), -jnp.inf), -1))
    out = rnd(jnp.einsum("kgqp,pkd->qkgd", probs, v)).reshape(t, heads, d)
    if faults.get("gate", True):
        out = rnd(out * rnd(jax.nn.sigmoid(u @ p["wg"]))[:, :, None])
    return rnd(out.reshape(t, heads * d) @ p["wo"])


def swiglu(u, w1, w3, w2, rnd=_same):
    return rnd(rnd(jax.nn.silu(rnd(u @ w1)) * rnd(u @ w3)) @ w2)


def route_weights(scores, taken, cfg: dict):
    """The weights ``[T, k]`` of the experts ``taken``: their scores,
    normalised, scaled."""
    weights = jnp.take_along_axis(scores, taken, axis=-1)
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * float(cfg["moe_routed_scaling_factor"])


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


def layer(h, p, i: int, cfg: dict, held=None, faults: dict = None,
          forced=None):
    """Layer ``i`` over one row ``[T, H]``; also the experts chosen
    (``None`` in a dense layer)."""
    faults = faults or {}
    rnd = faults.get("round_to") or _same
    eps = eps_of(cfg)
    h = rnd(h + attention(
        rnd(rms_norm(h, p["attn_norm"], eps)), p["attn"],
        cfg["layer_types"][i], int(cfg["num_attention_heads_per_layer"][i]),
        cfg, faults))
    u = rnd(rms_norm(h, p["ffn_norm"], eps))
    chosen = None
    if cfg["mlp_layer_types"][i] == "dense":
        m = swiglu(u, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"], rnd)
    else:
        m, chosen = experts(u, p["ffn"], cfg, held, faults, forced)
    return rnd(h + m), chosen


def left(h, p, i: int, cfg: dict):
    """The keys and values a row ``[T, H]`` leaves in layer ``i``."""
    return keys_values(rms_norm(h, p["attn_norm"], eps_of(cfg)), p["attn"],
                       cfg["layer_types"][i], cfg)


def head(h, head_w, norm_f, cfg: dict):
    return rms_norm(h, norm_f, eps_of(cfg)) @ head_w.T


def forward(tokens, cfg: dict, embed, head_w, norm_f, layer_weights,
            held=None, faults: dict = None):
    """Logits ``[T, V]`` of one row of token ids ``[T]`` and the experts
    chosen in each expert layer ``[T, expert layers, k]``.
    ``layer_weights(i)`` gives layer ``i``'s weights (float32)."""
    with jax.default_matmul_precision(HIGHEST):
        h = embed[tokens]
        routes = []
        for i in range(int(cfg["num_hidden_layers"])):
            h, chosen = jax.jit(lambda h, p, i=i: layer(
                h, p, i, cfg, held, faults))(h, layer_weights(i))
            if chosen is not None:
                routes.append(chosen)
        return head(h, head_w, norm_f, cfg), jnp.stack(routes, 1)
