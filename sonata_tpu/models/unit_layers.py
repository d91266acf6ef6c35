"""What the backbones of a unit voice share (:mod:`.lfm2`, :mod:`.sdar`,
:mod:`.nemotron_h`, :mod:`.pangu_moe`, :mod:`.laguna`, :mod:`.gigachat`):
the pieces of a layer, the expert layer, the head, the sampler and what a
prompt and a step leave a slot with beside its layers' state.

What differs between the backbones that call a piece is a field of their
configuration, read when a program is built: the router's scoring
(``router_scoring``), ``head_dim`` where it is not ``hidden_size / heads``,
a head of its own (``tie_word_embeddings: false``), an expert's form
(``expert_act``) and, in the layer's weights, a shared expert
(``p["shared"]``).

**Precision** (what the comparison of a benchmark cell is held to): weights
are bfloat16; every matrix product takes bfloat16 inputs and accumulates in
float32 (the products of the expert layer's router excepted: float32 at
``highest``, so that near-ties flip rarely); the residual stream, RMS norms,
router scores, the softmax and the logits are float32; keys and values are
cached in bfloat16, and the softmax's probabilities enter their product with
the values as bfloat16.

**The expert layer knows which experts it holds** (``held = (first,
count)``): it scores and chooses over all ``num_experts``, and its result is
the sum over the chosen experts *it holds* of weight x expert(u): with
``(0, num_experts)`` the whole layer, otherwise the share of one chip of an
expert-parallel deployment (the shares of all chips add up to the whole
layer; nothing stands in for the absent ones).  Only the chosen experts'
products are computed: the assignments are sorted by expert and go through
:func:`~sonata_tpu.ops.grouped_matmul.grouped_matmul`, which means what
``jax.lax.ragged_dot`` means and off a TPU is it; on a TPU, at the few rows
an expert a unit voice's programs have, it is this repo's kernel
(:func:`expert_matmul` says which a program of a given size runs).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.grouped_matmul import grouped_matmul, implementation, lanes
from ..ops.slot_attention import slot_attention, write_rows, write_slot

Params = dict
BF16 = jnp.bfloat16
F32 = jnp.float32



@dataclasses.dataclass(frozen=True)
class UnitIds:
    """How the vocabulary is split: ids below ``first_id`` are the prompt's
    (phoneme ids), the others are acoustic units; ``stop_id`` is the unit
    that ends a row; ``mask_id``, where a backbone has one, stands for a
    position not yet decided and is never a unit."""

    first_id: int
    stop_id: int
    mask_id: Optional[int] = None


# -- a layer's pieces ---------------------------------------------------------

def mm(x, w):
    """bfloat16 inputs, float32 accumulation."""
    return jnp.dot(x.astype(BF16), w, preferred_element_type=F32)


def rms_norm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def apply_rope(x, positions, theta: float):
    """``x`` ``[N, heads, d]`` at ``positions`` ``[N]`` (rotate-half)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _qkv(u, p, cfg, positions):
    n = u.shape[0]
    heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    qkv = mm(u, p["wqkv"])
    q = qkv[:, :heads * d].reshape(n, heads, d)
    k = qkv[:, heads * d:(heads + kv) * d].reshape(n, kv, d)
    v = qkv[:, (heads + kv) * d:].reshape(n, kv, d)
    q = apply_rope(rms_norm(q, p["q_norm"], cfg.norm_eps), positions,
                   cfg.rope_theta)
    k = apply_rope(rms_norm(k, p["k_norm"], cfg.norm_eps), positions,
                   cfg.rope_theta)
    return q, k.astype(BF16), v.astype(BF16)


def block_mask(positions, block: int = 1):
    """``[T, T]``: row ``i`` sees column ``j`` iff ``j``'s block of ``block``
    positions is not after ``i``'s (1: causal)."""
    at = positions if block == 1 else positions // block
    return at[:, None] >= at[None, :]


def attn_op_seq(u, p, cfg, block: int = 1, qkv=_qkv):
    """A row's prompt whole; also its keys and values.  Causal between
    blocks of ``block`` positions and whole inside one (1: causal).
    ``qkv`` makes queries, keys and values of ``u`` (a backbone without
    head norms or rotary brings its own)."""
    with jax.named_scope("attn_op"):
        t = u.shape[0]
        kv, d = cfg.num_key_value_heads, cfg.head_dim
        g = cfg.num_attention_heads // kv
        pos = jnp.arange(t)
        q, k, v = qkv(u, p, cfg, pos)
        q = q.reshape(t, kv, g, d).astype(BF16)
        scores = jnp.einsum("qkgd,pkd->kgqp", q, k,
                            preferred_element_type=F32) / jnp.sqrt(F32(d))
        probs = jax.nn.softmax(
            jnp.where(block_mask(pos, block), scores, -jnp.inf), -1)
        out = jnp.einsum("kgqp,pkd->qkgd", probs.astype(BF16), v,
                         preferred_element_type=F32)
        return mm(out.reshape(t, -1), p["wo"]), k, v


def attn_op_step(u, p, cfg, k_buf, v_buf, pos, qkv=_qkv):
    """One token of every slot at its position ``pos`` ``[S]``, through
    the slots' keys and values (``ops/slot_attention.py`` says where they
    lie)."""
    with jax.named_scope("attn_op"):
        s = pos.shape[0]
        kv, d = cfg.num_key_value_heads, cfg.head_dim
        g = cfg.num_attention_heads // kv
        q, k, v = qkv(u, p, cfg, pos)
        k_buf = write_rows(k_buf, k[:, None], pos[:, None])
        v_buf = write_rows(v_buf, v[:, None], pos[:, None])
        out = slot_attention(q.reshape(s, 1, kv, g, d), k_buf, v_buf,
                             pos + 1)
        return mm(out.reshape(s, -1), p["wo"]), k_buf, v_buf


def swiglu(u, w13, w2):
    a, b = jnp.split(mm(u, w13), 2, axis=-1)
    return mm(jax.nn.silu(a) * b, w2)


def dense_ffn(u, p):
    with jax.named_scope("dense_ffn"):
        return swiglu(u, p["w13"], p["w2"])


# -- the expert layer ---------------------------------------------------------

def route(u, p, cfg):
    """The experts chosen ``[N, k]`` and their weights ``[N, k]``.
    ``sigmoid``: selection by ``sigmoid + expert_bias``, weights the
    unbiased sigmoid of the chosen, normalised.  ``softmax``: the largest
    of a softmax over all experts, renormalised over the chosen where
    ``norm_topk_prob``."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(u, p["router"], precision="highest")
        if cfg.router_scoring == "softmax":
            scores = jax.nn.softmax(logits, -1)
            _, chosen = lax.top_k(scores, cfg.num_experts_per_tok)
            weights = jnp.take_along_axis(scores, chosen, axis=-1)
            if cfg.norm_topk_prob:
                weights = weights / jnp.sum(weights, -1, keepdims=True)
            return chosen, weights
        scores = jax.nn.sigmoid(logits)
        pick = scores + p["expert_bias"] if cfg.use_expert_bias else scores
        _, chosen = lax.top_k(pick, cfg.num_experts_per_tok)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
        return chosen, weights * cfg.routed_scaling_factor


def _expert_act(up, cfg):
    """What stands between an expert's two products (``expert_act``):
    ``relu2``, ``swiglu`` or ``swiglu_clamped`` (the gate's pre-activation
    at most ``cfg.swiglu_limit``, the up branch within ``+-`` it)."""
    if cfg.expert_act == "relu2":
        return jnp.square(jax.nn.relu(up))
    a, b = jnp.split(up, 2, axis=-1)
    if cfg.expert_act == "swiglu_clamped":
        limit = cfg.swiglu_limit
        a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
    return jax.nn.silu(a) * b


def pad_experts(w_up, w_down):
    """Ungated experts ``[E, H, I]``, ``[E, I, H]`` laid out in whole lanes
    of ``I``: zero columns of ``w_up`` give zeros (``relu(0)^2``) that meet
    zero rows of ``w_down``, so the layer's result is the same to the last
    bit, and the products are shapes :func:`grouped_matmul`'s kernel takes
    where ``I`` itself is not (``ops/grouped_matmul.py`` says why)."""
    pad = lanes(w_up.shape[-1]) - w_up.shape[-1]
    return (jnp.pad(w_up, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(w_down, ((0, 0), (0, pad), (0, 0))))


def held_rows(cfg, tokens: int, held: Optional[tuple]) -> int:
    """Rows the expert products of a program over ``tokens`` tokens run on:
    all ``tokens x k`` assignments, or, where the layer holds a thin share
    of the router's experts, a static bound on what the held experts get:
    twice the ``tokens x k x count / num_experts`` an even router gives
    them and half a row tile more, in row tiles of 128.  A share of a half
    is not thin by this (the bound is no shorter than all the rows): its
    programs are what they were."""
    rows = tokens * cfg.num_experts_per_tok
    if held is None:
        return rows
    even = rows * held[1] / cfg.num_experts
    return min(rows, lanes(int(2 * even) + 64))


def _held_experts(x, p, cfg, take, top: int, sizes, weights, mine):
    """The assignments ``take`` (indices into the ``[N x k]`` assignments,
    sorted by held expert: ``sizes``) through the experts: each one's
    token of ``x`` ``[N, H]``, its result times its weight, zero where not
    ``mine``.  Rows behind the last group come back from the products as
    anything: masked here."""
    up = grouped_matmul(x[take // top], p["w13"], sizes,
                        preferred_element_type=F32)
    y = grouped_matmul(_expert_act(up, cfg).astype(BF16), p["w2"], sizes,
                       preferred_element_type=F32)
    return jnp.where(mine[take][:, None],
                     y * weights.reshape(-1)[take][:, None], 0.0)


def moe_ffn(u, p, cfg, held: Optional[tuple] = None,
            valid=None):
    """The expert layer over tokens ``u`` ``[N, H]``.

    Returns the layer's output (the sum over each token's chosen experts
    that this layer holds and, where the layer has one (``p["shared"]``),
    the shared expert every token takes), the experts chosen ``[N, k]``,
    and the load over the valid tokens: distinct experts chosen, the most
    assignments any one expert got, assignments in all; where ``held`` is
    given also the distinct experts chosen *among the held* and the
    assignments that fell on them.  ``valid`` ``[N]`` masks padding and
    empty slots: they cost no expert product and count for nothing.  An
    expert's form is the configuration's ``expert_act``.

    **A thin share** (:func:`held_rows` below all the assignments: 8 of
    256 experts held) gathers, multiplies and adds back only the sorted
    assignments' first :func:`held_rows`, which are the held experts'
    whenever those got no more; a launch in which they got more takes the
    full-length path inside the same program (no held assignment is ever
    left out), and the load says so with a sixth number, 1 for such a
    launch."""
    n, top = u.shape[0], cfg.num_experts_per_tok
    first, count = held if held is not None else (0, cfg.num_experts)
    if p["w13"].shape[0] != count:
        raise ValueError(f"held = {held} but the layer holds "
                         f"{p['w13'].shape[0]} experts")
    chosen, weights = route(u, p, cfg)
    with jax.named_scope("moe_experts"):
        valid = jnp.ones((n,), bool) if valid is None else valid
        expert = chosen.reshape(-1)
        counted = jnp.repeat(valid, top)
        local = expert - first
        mine = counted & (local >= 0) & (local < count)
        # assignments sorted by expert, those of experts held elsewhere (and
        # of padding) last, outside every group
        group = jnp.where(mine, local, count)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=count + 1)[:count].astype(
            jnp.int32)
        x = u.astype(BF16)

        def whole():
            y = _held_experts(x, p, cfg, order, top, sizes, weights, mine)
            return y[jnp.argsort(order)].reshape(n, top, -1).sum(1)

        bound = held_rows(cfg, n, held)
        fits = None     # not thin: all the rows, nothing to overflow
        if bound == n * top:
            out = whole()
        else:
            def thin():
                take = order[:bound]
                y = _held_experts(x, p, cfg, take, top, sizes, weights, mine)
                # each row back to its token: a 0/1 matrix, exact at
                # ``highest`` (a token may hold two rows; a scatter would
                # walk them one by one)
                back = (jnp.arange(n)[:, None] == (take // top)[None, :])
                return jnp.dot(back.astype(F32), y, precision="highest")

            fits = jnp.sum(sizes) <= bound
            out = lax.cond(fits, thin, whole)
        loads = jnp.bincount(jnp.where(counted, expert, cfg.num_experts),
                             length=cfg.num_experts + 1)[:cfg.num_experts]
        load = [jnp.sum(loads > 0), jnp.max(loads), jnp.sum(loads)]
        if held is not None:
            load += [jnp.sum(sizes > 0), jnp.sum(sizes)]
        if fits is not None:
            load.append(jnp.logical_not(fits))
        load = jnp.stack(load).astype(jnp.int32)
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            out = out + mm(_expert_act(mm(u, p["shared"]["w_up"]), cfg),
                           p["shared"]["w_down"])
    return out, chosen, load


def expert_matmul(cfg, tokens: int, held: Optional[tuple] = None) -> str:
    """What the expert products of a program over ``tokens`` tokens run on
    this backend: ``"grouped"`` (this repo's kernel, both products) or
    ``"ragged_dot"``.  The shapes are :func:`moe_ffn`'s (ungated experts
    lie in whole lanes: :func:`pad_experts`; a thin share's rows are
    :func:`held_rows`: what all but an overflowing launch runs)."""
    rows = held_rows(cfg, tokens, held)
    count = held[1] if held is not None else cfg.num_experts
    h, i = cfg.hidden_size, cfg.moe_intermediate_size
    i, up = (lanes(i),) * 2 if cfg.expert_act == "relu2" else (i, 2 * i)
    both = {implementation(rows, count, k, n, BF16)
            for k, n in ((h, up), (i, h))}
    return "grouped" if both == {"grouped"} else "ragged_dot"


# -- the head and the sampler -------------------------------------------------

def _head(h, params, cfg):
    """Logits of ``h`` ``[N, H]``: the final norm, then the embedding's
    transpose or, untied, the head's own matrix (``[V, H]`` too)."""
    with jax.named_scope("head"):
        u = rms_norm(h, params["norm_f"], cfg.norm_eps)
        w = params["embed"] if cfg.tie_word_embeddings else params["head"]
        return lax.dot_general(u.astype(BF16), w,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def allowed_ids(vocab: int, units: UnitIds):
    """``[V]``: the ids a row may choose."""
    ids = jnp.arange(vocab)
    allowed = (ids >= units.first_id) & (ids != units.stop_id)
    if units.mask_id is not None:
        allowed &= ids != units.mask_id
    return allowed


def _scaled_and_noisy(logits, temperature, key, units: UnitIds) -> tuple:
    """``logits`` ``[N, V]`` over the row's temperature with every id that
    is no unit at ``-inf``, and the same plus Gumbel noise: the arg-max of
    the second is a draw from the softmax of the first.  A row whose
    ``temperature`` is 0 divides by 1 and gains no noise: its arg-max is
    the largest allowed logit's, to the bit."""
    drawn = temperature > 0
    scale = lax.select(drawn, temperature, jnp.ones_like(temperature))
    allowed = jnp.broadcast_to(allowed_ids(logits.shape[-1], units),
                               logits.shape)
    scaled = lax.select(allowed, logits,
                        jnp.full_like(logits, -jnp.inf)) / scale[:, None]
    noise = jax.random.gumbel(key, logits.shape, F32)
    return scaled, scaled + lax.select(
        jnp.broadcast_to(drawn[:, None], logits.shape), noise,
        jnp.zeros_like(noise))


def sample(logits, temperature, key, units: UnitIds):
    """A unit id per row of ``logits`` ``[N, V]``: the largest logit over
    the unit ids where ``temperature`` is 0, else a draw from
    ``softmax(logits / temperature)`` over them, both as one arg-max
    (:func:`_scaled_and_noisy`) that reads the logits where the head left
    them: :func:`choose`'s id without what a step does not read (every
    step program traces this, and a start pays for each equation).  The
    stop unit is suppressed: a row ends at its frame budget, which the
    host counts."""
    noisy = _scaled_and_noisy(logits, temperature, key, units)[1]
    return jnp.argmax(noisy, -1).astype(jnp.int32)


def _pick(a, b):
    """Of two candidates ``(noisy, place, plain there, largest plain)``
    the one :func:`jnp.argmax` keeps: the larger, a NaN before a number,
    the first of equals; and the larger of their largest."""
    (best_a, at_a, plain_a, top_a), (best_b, at_b, plain_b, top_b) = a, b
    b_wins = lax.bitwise_or(lax.gt(best_b, best_a), lax.ne(best_b, best_b))
    b_first = lax.bitwise_or(b_wins, lax.bitwise_and(
        lax.eq(best_b, best_a), lax.lt(at_b, at_a)))
    return (lax.select(b_wins, best_b, best_a),
            lax.select(b_first, at_b, at_a),
            lax.select(b_first, plain_b, plain_a), lax.max(top_a, top_b))


def choose(logits, temperature, key, units: UnitIds) -> tuple:
    """:func:`sample`'s id per row of ``logits`` ``[N, V]`` and the
    log-probability of that id under ``softmax(logits / temperature)`` over
    the unit ids (``[N]`` int32, ``[N]`` float32): the reduction that takes
    the arg-max also carries the scaled logit there and the row's largest,
    one more reading of the logits sums the exponentials; no
    ``log_softmax`` is written out."""
    scaled, noisy = _scaled_and_noisy(logits, temperature, key, units)
    _, ids, at, top = lax.reduce(
        (noisy, lax.broadcasted_iota(jnp.int32, noisy.shape, 1), scaled,
         scaled), (-jnp.inf, jnp.int32(0), -jnp.inf, -jnp.inf), _pick, (1,))
    total = jnp.sum(jnp.exp(scaled - top[:, None]), -1)
    return ids, at - top - jnp.log(total)


def step_key(seed: int, step_no):
    """The key of launch ``step_no``'s draws, of the ``rbg`` implementation:
    its ``[N, V]`` words are the platform's own generator's, one operation
    (on a v5e 0.26 ms for 256 rows of 151 936 ids), where threefry's
    arithmetic inside the choice's fusion took 0.78 of that fusion's 0.99
    (PERF.md section 6, PR 46).  The distribution is the same."""
    return jax.random.fold_in(jax.random.key(seed, impl="rbg"), step_no)


# -- what a prompt and a step leave a slot with -------------------------------

def join(cache: dict, slot, n, logits, routes: list, temperature, key,
         units: UnitIds) -> dict:
    """What a prompt leaves a slot with beside its layers' state: the row's
    first unit, sampled from the ``logits`` ``[1, V]`` at the prompt's last
    position, its place, its count, and the experts its ``[T]`` tokens
    chose."""
    unit = sample(logits, temperature[None], key, units)[0]
    cache["token"] = cache["token"].at[slot].set(unit)
    cache["pos"] = cache["pos"].at[slot].set(n)
    cache["count"] = cache["count"].at[slot].set(1)
    cache["units"] = cache["units"].at[slot, 0].set(unit)
    cache["routes"] = write_slot(cache["routes"], jnp.stack(routes, 1), slot)
    return cache


def advance(cache: dict, live, logits, routes: list, temperature, step_no,
            units: UnitIds, seed: int) -> dict:
    """What a step leaves every slot with beside its layers' state: the
    live rows' next unit, sampled from ``logits`` ``[S, V]``, one place and
    one unit more, and the experts their tokens chose."""
    pos = cache["pos"]
    unit = sample(logits, temperature, step_key(seed, step_no), units)
    rows = jnp.arange(live.shape[0])
    span = cache["units"].shape[1]
    cache["routes"] = write_rows(cache["routes"],
                                 jnp.stack(routes, 1)[:, None], pos[:, None])
    cache["units"] = cache["units"].at[
        rows, jnp.minimum(cache["count"], span - 1)].set(
        jnp.where(live, unit, 0))
    cache["token"] = jnp.where(live, unit, cache["token"])
    # an empty slot stays where it is, inside the cache
    cache["pos"] = jnp.where(live, jnp.minimum(pos + 1, span - 1), pos)
    cache["count"] = jnp.where(live, cache["count"] + 1, cache["count"])
    return cache


def advance_and_join(params: Params, cache: dict, h, routes: list, live,
                     temperature, step_no, n, slot, row_temperature, row_key,
                     cfg, units: UnitIds, seed: int) -> tuple:
    """The end of a step that carried an arrival, from ``h`` ``[S + T, H]``
    behind the last layer (the slots' rows, then the prompt's): the head
    over the ``S`` rows and the prompt's last position together (its matrix
    read once), then :func:`advance` of the live rows and, after it so
    that the prompt's write is the last word on ``slot``, :func:`join`.
    Returns the cache and the logits ``[S + 1, V]``: the slots' rows, then
    the prompt's (left in one array: the ``S`` rows apart would be a copy
    of all of them, 134 MB at 256 slots of 131 072 ids)."""
    s = live.shape[0]
    last = lax.dynamic_slice_in_dim(h, s + n - 1, 1, axis=0)
    logits = _head(jnp.concatenate([h[:s], last]), params, cfg)
    cache = advance(cache, live, logits[:s], [r[:s] for r in routes],
                    temperature, step_no, units, seed)
    cache = join(cache, slot, n, logits[s:], [r[s:] for r in routes],
                 row_temperature, row_key, units)
    return cache, logits
