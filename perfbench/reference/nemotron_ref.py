"""Plain reference of the Nemotron-H backbone's forward pass (``model_type:
nemotron_h``;
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the whole sequence of one row
at once, no cache, no batching, no kernels, no chunks; the Mamba layer is
the recurrence itself, a position at a time (``lax.scan``); every held
expert is computed for every token and weighted (zero where it was not
chosen).  Nothing is imported from the program.  The caller hands the
weights in, one layer at a time.

Every layer is one mixer behind one norm, ``h += mixer(rms(h))``, the mixer
named by a character of ``hybrid_override_pattern`` (eps
``layer_norm_epsilon``, no bias but the convolution's):

- ``M``: ``[z | xBC | dt] = u in_proj``; ``xBC = silu(causal depthwise
  conv(xBC, kernel conv_kernel) + conv_b)``; ``[x | B | C] = xBC``, ``x``
  ``[heads, P]`` (``heads x P`` channels: ``mamba_num_heads x
  mamba_head_dim``, not ``expand x hidden``), ``B``, ``C`` ``[n_groups, N]``,
  head ``j`` reading group ``j // (heads / n_groups)``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y = norm_w * rms_norm(y *
  silu(z))`` over each group's channels (the gate before the norm);
  ``out = y out_proj``.
- ``*``: grouped-query attention, causal, scale ``head_dim ** -0.5``, **no
  rotary and no other position encoding** (the family's published modeling
  code applies none: ``rope_theta`` and ``partial_rotary_factor`` are
  unread), no head norms.
- ``E``: ``s = sigmoid(u router)``; the experts chosen are ``top_k(s +
  e_score_correction_bias)`` (``n_group`` 1, ``topk_group`` 1: no group
  limit); their weights are the unbiased ``s`` of the chosen, divided by
  their sum + 1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``;
  an expert is ``relu(u w_up)^2 w_down``; a shared expert of the same form
  is added for every token.
- a final RMS norm, then the head, a matrix of its own.

Departures from the published graph: the residual stream is float32
(``residual_in_fp32: false`` keeps it in the weights' bfloat16 there);
``1e-20`` stands in the normalisation as published, where the program's
shared router (``lfm2.route``) has ``1e-6``; ``held = (first, count)`` gives
the share of an expert layer that one chip of an expert-parallel
deployment computes (routing over all experts, the sum over the chosen
experts it holds, the shared expert whole unless ``shared=False``);
``round_to`` rounds what the configuration states as float32 (the residual
stream, router scores, the softmax, the recurrent state) to another type,
which is how the comparison's lower-precision control is computed, and
``round_state`` the recurrent state alone, each step; ``state0`` starts the
Mamba layers from a state that is not zero (what a slot not cleared between
rows would give), and ``length`` stops the state where a padded row's real
tokens end (``dt`` is 0 from there on), so that what a row *leaves* can be
read; ``forced`` ``[T, k]`` makes an expert layer compute the
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
    return float(cfg["layer_norm_epsilon"])


def mamba(u, p, cfg: dict, round_state=None, state0=None, length=None):
    """One Mamba-2 layer over one row ``[T, H]``.  ``state0``: the
    recurrent state ``[heads, P, N]`` and the convolution's columns
    ``[conv_kernel - 1, C]`` the row starts from (zero where ``None``).
    Also what the row leaves, as such a pair: after ``length`` positions
    where that is given (the state alone; the columns are the sequence's
    last), else after all."""
    t = u.shape[0]
    heads, hp = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    groups, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    k = int(cfg["conv_kernel"])
    d = heads * hp
    zxbcdt = u @ p["in_proj"]
    z, xbc, dt = (zxbcdt[:, :d], zxbcdt[:, d:d + d + 2 * groups * n],
                  zxbcdt[:, 2 * d + 2 * groups * n:])
    before = jnp.zeros((k - 1, xbc.shape[1])) if state0 is None \
        else state0[1]
    padded = jnp.concatenate([before, xbc], 0)
    columns = padded[t:]
    conv = sum(padded[j:j + t] * p["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[:, :d].reshape(t, heads, hp)
    b = jnp.repeat(xbc[:, d:d + groups * n].reshape(t, groups, n),
                   heads // groups, axis=1)
    c = jnp.repeat(xbc[:, d + groups * n:].reshape(t, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    if length is not None:
        dt = jnp.where((jnp.arange(t) < length)[:, None], dt, 0.0)
    a = -jnp.exp(p["A_log"])

    def one(state, at):
        x_t, b_t, c_t, dt_t = at
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if round_state is not None:
            state = round_state(state)
        return state, jnp.sum(state * c_t[:, None, :], -1)

    first = jnp.zeros((heads, hp, n)) if state0 is None else state0[0]
    last, y = lax.scan(one, first, (x, b, c, dt))
    y = (y + p["D"][:, None] * x).reshape(t, d)
    g = (y * jax.nn.silu(z)).reshape(t, groups, d // groups)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps_of(cfg))
    return (g.reshape(t, d) * p["norm"]) @ p["out_proj"], (last, columns)


def attention(u, p, cfg: dict, round_to=None):
    t = u.shape[0]
    heads, kv = int(cfg["num_attention_heads"]), int(
        cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    q = (u @ p["wq"]).reshape(t, heads, d)
    k = jnp.repeat((u @ p["wk"]).reshape(t, kv, d), heads // kv, axis=1)
    v = jnp.repeat((u @ p["wv"]).reshape(t, kv, d), heads // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * float(d) ** -0.5
    pos = jnp.arange(t)
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    if round_to is not None:
        probs = round_to(probs)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * d) \
        @ p["wo"]


def expert(u, w_up, w_down):
    return jnp.square(jax.nn.relu(u @ w_up)) @ w_down


def route_weights(scores, taken, cfg: dict):
    """The weights ``[T, k]`` of the experts ``taken``: their unbiased
    scores, normalised, scaled."""
    weights = jnp.take_along_axis(scores, taken, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights * float(cfg["routed_scaling_factor"])


def route(u, p, cfg: dict, round_to=None):
    """``(chosen [T, k], weights [T, k], scores [T, E])``."""
    scores = jax.nn.sigmoid(u @ p["router"])
    if round_to is not None:
        scores = round_to(scores)
    _, chosen = lax.top_k(scores + p["e_score_correction_bias"],
                          int(cfg["num_experts_per_tok"]))
    return chosen, route_weights(scores, chosen, cfg), scores


def experts(u, p, cfg: dict, held=None, round_to=None, forced=None,
            shared: bool = True):
    """The expert layer's output ``[T, H]`` and the experts chosen
    ``[T, k]``.  ``held = (first, count)``: only the chosen experts among
    ``first .. first + count - 1`` add to the result (``p["w_up"]`` holds
    those ``count`` experts); ``None`` is the whole layer.  With ``forced``
    ``[T, k]`` those experts are computed in the chosen ones' place.
    ``shared=False`` leaves the shared expert out."""
    chosen, weights, scores = route(u, p, cfg, round_to)
    taken = chosen
    if forced is not None:
        taken, weights = forced, route_weights(scores, forced, cfg)
    first, count = held if held is not None else (0, p["w_up"].shape[0])

    def one(args):
        e, w_up, w_down = args
        gate = jnp.sum(jnp.where(taken == first + e, weights, 0.0), -1)
        return gate[:, None] * expert(u, w_up, w_down)

    out = jnp.sum(lax.map(one, (jnp.arange(count), p["w_up"], p["w_down"])),
                  0)
    if shared:
        out = out + expert(u, p["shared_up"], p["shared_down"])
    return out, chosen


def mamba_left(h, p, cfg: dict):
    """What a row ``[T, H]`` leaves in Mamba layer ``p``: its recurrent
    state and the convolution's last columns."""
    return mamba(rms_norm(h, p["norm"], eps_of(cfg)), p["mixer"], cfg)[1]


def layer(h, p, kind: str, cfg: dict, held=None, round_to=None, forced=None,
          round_state=None, state0=None, shared: bool = True, length=None):
    """One layer over one row ``[T, H]``; also the experts chosen (``None``
    unless the layer is ``E``) and the recurrent state the row leaves
    (``None`` unless it is ``M``)."""
    rnd = round_to if round_to is not None else (lambda a: a)
    u = rms_norm(h, p["norm"], eps_of(cfg))
    chosen = left = None
    if kind == "M":
        if round_state is None and round_to is not None:
            round_state = round_to
        out, (left, _) = mamba(u, p["mixer"], cfg, round_state, state0,
                               length)
    elif kind == "*":
        out = attention(u, p["mixer"], cfg, round_to)
    else:
        out, chosen = experts(u, p["mixer"], cfg, held, round_to, forced,
                              shared)
    return rnd(h + out), chosen, left


def head(h, head_w, norm_f, cfg: dict):
    return rms_norm(h, norm_f, eps_of(cfg)) @ head_w.T


def forward(tokens, cfg: dict, embed, head_w, norm_f, layer_weights,
            held=None, round_to=None):
    """Logits ``[T, V]`` of one row of token ids ``[T]`` and the experts
    chosen in each expert layer ``[T, expert layers, k]``.
    ``layer_weights(i)`` gives layer ``i``'s weights (float32)."""
    fns: dict = {}
    with jax.default_matmul_precision(HIGHEST):
        h = embed[tokens]
        routes = []
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            if kind not in fns:
                fns[kind] = jax.jit(lambda h, p, kind=kind: layer(
                    h, p, kind, cfg, held, round_to))
            h, chosen, _ = fns[kind](h, layer_weights(i))
            if chosen is not None:
                routes.append(chosen)
        return head(h, head_w, norm_f, cfg), jnp.stack(routes, 1)
