"""A Laguna backbone (``model_type: laguna``) for step-wise generation: two
kinds of attention layer in one backbone, each with its own head count, its
own rotary rule and its own cache, a gate on the attention's result, and an
expert layer of whose routed experts this chip may hold a share.

Every layer is ``h += attn(rms(h)); h += ffn(rms(h))`` on the float32
residual ``h``.  Layer ``l``'s kind is ``layer_types[l]`` and its query
heads ``num_attention_heads_per_layer[l]``, over ``num_key_value_heads``
heads of ``head_dim`` (no bias, no norm on queries or keys):

- ``full_attention``: causal over every position.  Rotary on the first
  ``partial_rotary_factor`` of a head's dimensions, YaRN
  (:func:`yarn_inv_freq`: the fast dimensions keep their pace, the slow
  ones run at ``1 / factor`` of it, a ramp between), ``cos`` and ``sin``
  times ``attention_factor`` at every position; the other dimensions pass.
- ``sliding_attention``: a query at position ``i`` sees ``i - window < j <=
  i``.  Plain rotary on all of a head's dimensions.

Scores ``q k^T / sqrt(head_dim)``.  **The gate** (``gating: true``): ``a =
sigmoid(u W_g)``, one number a query head, times the head's result before
``W_o``.  ``ffn`` is a dense SwiGLU where ``mlp_layer_types`` says ``dense``
and else the expert layer: :func:`~.lfm2.route`'s sigmoid router with no
bias (the ``num_experts_per_tok`` largest scores, renormalised, times
``moe_routed_scaling_factor``; weights on the experts' results), SwiGLU
experts, and a shared expert every token takes.  :func:`~.lfm2.moe_ffn` runs
it; ``held = (first, count)`` says which of the router's experts this chip
holds (the configuration's ``expert_parallel`` block), and what the others
would add is left out.  A final RMS norm, then a head of its own.

**Two caches in one slot table** (:func:`new_cache`): per layer keys and
values, bfloat16, after their rotary, a place one row of whole lanes
(``ops/slot_attention.py``).  A full layer keeps every position of a slot; a
sliding layer keeps a **ring** of ``sliding_window`` places: a step writes
place ``pos mod window`` and reads ``min(pos + 1, window)`` places, through
the reader every other buffer has (that module owns both rules).  A prompt
attends over itself by an einsum under the causal mask and, in a sliding
layer, the band.

**Precision**, as :mod:`.lfm2` states it, and: rotary (YaRN's factor with
it) and the gate's sigmoid are float32.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.slot_attention import ring_upto, slot_attention, stored_shape, \
    write_rows, write_slot
from .unit_backbone import Description, TokenRows, token_step_programs
from .unit_layers import BF16, F32, UnitIds, _head, advance, \
    advance_and_join, block_mask, dense_ffn, join, mm, moe_ffn, rms_norm

Params = dict
FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One kind of layer's rotary rule: ``dims`` of a head's dimensions are
    rotated (by halves) at the paces ``inv_freq`` ``[dims / 2]``, ``cos``
    and ``sin`` times ``factor``."""

    dims: int
    inv_freq: tuple
    factor: float = 1.0


def yarn_inv_freq(dims: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's paces for ``dims`` rotated dimensions: ``base_i = theta ^
    (-2i / dims)`` where a rotation makes ``beta_fast`` turns and more in
    ``original`` positions, ``base_i / factor`` where it makes ``beta_slow``
    and fewer, a linear ramp over ``i`` between the two."""
    base = theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)

    def turns_at(turns: float) -> float:
        return dims * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dims - 1)
    ramp = np.clip((np.arange(dims // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return base / factor * (1.0 - keep) + base * keep


def rotary_of(rule: dict, head_dim: int) -> Rotary:
    """A kind's entry of ``rope_parameters`` as a :class:`Rotary`."""
    dims = int(head_dim * float(rule.get("partial_rotary_factor", 1)))
    theta = float(rule["rope_theta"])
    kind = rule.get("rope_type", "default")
    if kind == "default":
        inv = theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)
        return Rotary(dims, tuple(inv))
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is neither default nor yarn")
    scale = float(rule["factor"])
    inv = yarn_inv_freq(
        dims, theta, scale, int(rule["original_max_position_embeddings"]),
        float(rule["beta_fast"]), float(rule["beta_slow"]))
    return Rotary(dims, tuple(inv), float(rule.get(
        "attention_factor") or 0.1 * math.log(scale) + 1.0))


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The backbone's published ``config.json`` keys that shape the graph
    (and, constant for the family, what :mod:`.lfm2`'s pieces ask)."""

    hidden_size: int
    layer_types: tuple
    heads_per_layer: tuple      #: query heads, layer by layer
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    rotary: tuple               #: ``((kind, Rotary), ...)``
    mlp_layer_types: tuple
    moe_intermediate_size: int
    num_experts: int            #: the router's width
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_eps: float
    vocab_size: int
    #: the routed experts this chip holds: ``(first, count)``
    held: tuple
    tie_word_embeddings: bool = False
    router_scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    use_expert_bias: bool = False
    expert_act: str = "swiglu"

    @classmethod
    def from_dict(cls, d: dict) -> "LagunaConfig":
        layers = int(d["num_hidden_layers"])
        kinds = tuple(d["layer_types"][:layers])
        heads = tuple(int(n) for n in
                      d["num_attention_heads_per_layer"][:layers])
        mlps = tuple(d["mlp_layer_types"][:layers])
        if not (len(kinds) == len(heads) == len(mlps) == layers) or set(
                kinds) - {FULL, SLIDING} or set(mlps) - {"dense", "sparse"}:
            raise ValueError(
                "layer_types, num_attention_heads_per_layer and "
                f"mlp_layer_types do not give {layers} known layers")
        kv = int(d["num_key_value_heads"])
        if any(n % kv for n in heads):
            raise ValueError(f"{kv} key-value heads do not divide {heads}")
        if not d.get("gating") or d.get("attention_bias") or d.get(
                "moe_apply_router_weight_on_input"):
            raise ValueError("only a gated attention without bias and "
                             "router weights on the experts' results are "
                             "supported")
        held_here = int(d["num_experts"])
        share = d.get("expert_parallel") or {
            "routed_experts": held_here, "held": [0, held_here]}
        held = tuple(int(v) for v in share["held"])
        if held[1] != held_here or held[0] < 0 or sum(held) > int(
                share["routed_experts"]):
            raise ValueError(f"held = {held} is not num_experts = "
                             f"{held_here} of {share['routed_experts']}")
        head_dim = int(d["head_dim"])
        return cls(
            hidden_size=int(d["hidden_size"]), layer_types=kinds,
            heads_per_layer=heads, num_key_value_heads=kv, head_dim=head_dim,
            sliding_window=int(d["sliding_window"]),
            rotary=tuple((kind, rotary_of(d["rope_parameters"][kind],
                                          head_dim))
                         for kind in (FULL, SLIDING)),
            mlp_layer_types=mlps,
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(share["routed_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            routed_scaling_factor=float(d["moe_routed_scaling_factor"]),
            norm_eps=float(d["rms_norm_eps"]),
            vocab_size=int(d["vocab_size"]), held=held,
            tie_word_embeddings=bool(d["tie_word_embeddings"]))

    def layers_of(self, kind: str) -> list:
        return [i for i, k in enumerate(self.layer_types) if k == kind]

    @property
    def expert_layers(self) -> list:
        return [i for i, k in enumerate(self.mlp_layer_types)
                if k == "sparse"]

    def rotary_rule(self, kind: str) -> Rotary:
        return dict(self.rotary)[kind]

    def places(self, kind: str, positions: int) -> int:
        """The places a slot of ``positions`` positions holds in a layer of
        ``kind``: all of them, or the ring's."""
        return positions if kind == FULL else min(self.sliding_window,
                                                  positions)

    @property
    def place_bytes(self) -> int:
        """Bytes of one place of one layer: keys and values, as stored."""
        return 2 * 2 * stored_shape(1, 1, self.num_key_value_heads,
                                    self.head_dim)[-1]


def pack_layer(raw: dict) -> Params:
    """One layer from its tensors under the reference's names (bfloat16) to
    the layout the programs read: ``wq | wk | wv | wg`` side by side (one
    product of ``u``), ``w1 | w3`` as ``w13`` (the shared expert's as
    ``w_up``); norms and the router float32."""
    attn, ffn = raw["attn"], raw["ffn"]
    ffn_p = {"w13": jnp.concatenate([ffn["w1"], ffn["w3"]], -1),
             "w2": ffn["w2"]}
    if "router" in ffn:
        ffn_p.update(
            router=ffn["router"].astype(F32),
            shared={"w_up": jnp.concatenate([ffn["shared_w1"],
                                             ffn["shared_w3"]], -1),
                    "w_down": ffn["shared_w2"]})
    return {"attn_norm": raw["attn_norm"].astype(F32),
            "ffn_norm": raw["ffn_norm"].astype(F32),
            "attn": {"wqkvg": jnp.concatenate(
                [attn["wq"], attn["wk"], attn["wv"], attn["wg"]], -1),
                "wo": attn["wo"]},
            "ffn": ffn_p}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def rotate(x, positions, rule: Rotary):
    """``x`` ``[N, heads, d]`` at ``positions`` ``[N]`` by ``rule``: its
    first ``rule.dims`` dimensions rotated by halves, the others as they
    are."""
    inv = jnp.asarray(rule.inv_freq, F32)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * rule.factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * rule.factor
    turned, passed = x[..., :rule.dims], x[..., rule.dims:]
    x1, x2 = turned[..., :rule.dims // 2], turned[..., rule.dims // 2:]
    turned = turned * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, passed], -1) if passed.shape[-1] \
        else turned


def qkvg(u, p, cfg: LagunaConfig, i: int, positions):
    """Row-wise over ``u`` ``[N, H]`` at ``positions`` ``[N]``, layer
    ``i``'s queries ``[N, n_q, d]`` (float32) and keys and values ``[N, kv,
    d]`` (the cache's type) after the kind's rotary, and the gate ``[N,
    n_q]``."""
    n = u.shape[0]
    heads, kv, d = cfg.heads_per_layer[i], cfg.num_key_value_heads, \
        cfg.head_dim
    rule = cfg.rotary_rule(cfg.layer_types[i])
    out = mm(u, p["wqkvg"])
    q = out[:, :heads * d].reshape(n, heads, d)
    k = out[:, heads * d:(heads + kv) * d].reshape(n, kv, d)
    v = out[:, (heads + kv) * d:(heads + 2 * kv) * d].reshape(n, kv, d)
    gate = jax.nn.sigmoid(out[:, (heads + 2 * kv) * d:])
    return (rotate(q, positions, rule),
            rotate(k, positions, rule).astype(BF16), v.astype(BF16), gate)


def band_mask(positions, window: int):
    """``[T, T]``: row ``i`` sees column ``j`` iff ``i - window < j <= i``."""
    return block_mask(positions) & (
        positions[:, None] - positions[None, :] < window)


def attend_seq(q, k, v, cfg: LagunaConfig, i: int):
    """A row's prompt over itself (``[T, ...]`` of :func:`qkvg`): causal
    and, in a sliding layer, inside the band.  Returns ``[T, n_q, d]``."""
    t, kv, d = q.shape[0], cfg.num_key_value_heads, cfg.head_dim
    pos = jnp.arange(t)
    seen = block_mask(pos) if cfg.layer_types[i] == FULL else band_mask(
        pos, cfg.sliding_window)
    scores = jnp.einsum("qkgd,pkd->kgqp", q.reshape(t, kv, -1, d).astype(
        BF16), k, preferred_element_type=F32) / jnp.sqrt(F32(d))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    out = jnp.einsum("kgqp,pkd->qkgd", probs.astype(BF16), v,
                     preferred_element_type=F32)
    return out.reshape(t, -1, d)


def attend_step(q, k, v, k_buf, v_buf, pos, cfg: LagunaConfig, i: int):
    """One token of every slot (``[S, ...]`` of :func:`qkvg`) at its
    position ``pos`` ``[S]``: its key and value are written (a sliding
    layer's buffers are rings) and the slot's places read.  Returns ``[S,
    n_q, d]`` and the buffers."""
    s, kv, d = q.shape[0], cfg.num_key_value_heads, cfg.head_dim
    ring = cfg.layer_types[i] == SLIDING
    k_buf = write_rows(k_buf, k[:, None], pos[:, None], ring=ring)
    v_buf = write_rows(v_buf, v[:, None], pos[:, None], ring=ring)
    upto = ring_upto(pos + 1, k_buf.shape[1]) if ring else pos + 1
    out = slot_attention(q.reshape(s, 1, kv, -1, d), k_buf, v_buf, upto)
    return out.reshape(s, -1, d), k_buf, v_buf


def write_prompt(buf, seq, n, slot, cfg: LagunaConfig, i: int):
    """A prompt's keys (or values) ``seq`` ``[T, kv, d]`` (``n`` real) into
    ``slot`` of layer ``i``'s buffer."""
    return write_slot(buf, seq, slot,
                      ring_n=n if cfg.layer_types[i] == SLIDING else None)


def _close(h, attended, gate, p, i: int, cfg: LagunaConfig, valid, routes,
           loads):
    """The layer from behind its attention's core: the gate, ``W_o``, and
    the feed-forward; an expert layer appends the experts chosen and its
    load."""
    with jax.named_scope("attn_op"):
        n = attended.shape[0]
        h = h + mm((attended * gate[:, :, None]).reshape(n, -1),
                   p["attn"]["wo"])
    u = rms_norm(h, p["ffn_norm"], cfg.norm_eps)
    if cfg.mlp_layer_types[i] == "dense":
        return h + dense_ffn(u, p["ffn"])
    out, chosen, load = moe_ffn(u, p["ffn"], cfg, cfg.held, valid)
    routes.append(chosen)
    loads.append(load)
    return h + out


# ---------------------------------------------------------------------------
# the generation state and the programs
# ---------------------------------------------------------------------------

def new_cache(cfg: LagunaConfig, slots: int, positions: int) -> dict:
    """The state of ``slots`` rows of at most ``positions`` tokens: per
    layer keys and values (every position in a full layer, a ring of
    ``sliding_window`` places in a sliding one), and per slot the next
    token, its position, the units sampled so far and the experts every
    token chose (unsigned bytes: the router has 256 outputs).  Rows lie as
    :func:`~sonata_tpu.ops.slot_attention.stored_shape` says."""
    shapes = [stored_shape(slots, cfg.places(kind, positions),
                           cfg.num_key_value_heads, cfg.head_dim)
              for kind in cfg.layer_types]
    return {
        "k": [jnp.zeros(shape, BF16) for shape in shapes],
        "v": [jnp.zeros(shape, BF16) for shape in shapes],
        "token": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "count": jnp.zeros((slots,), jnp.int32),
        "units": jnp.zeros((slots, positions), jnp.int32),
        "routes": jnp.zeros(stored_shape(
            slots, positions, len(cfg.expert_layers),
            cfg.num_experts_per_tok), jnp.uint8),
    }


def prefill(params: Params, cache: dict, ids, n, slot, temperature, key, *,
            cfg: LagunaConfig, units: UnitIds):
    """One row joins: its prompt ``ids`` ``[T]`` (``n`` real) runs whole,
    its keys and values go into ``slot`` (of a ring: the last ``window``
    positions) and its first unit is sampled from the logits at the prompt's
    last position.  Returns the cache, those logits ``[V]`` and the expert
    layers' load."""
    t = ids.shape[0]
    valid = jnp.arange(t) < n
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]))
    h = params["embed"][ids].astype(F32)
    pos = jnp.arange(t)
    routes, loads = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("attn_op"):
            q, k, v, gate = qkvg(rms_norm(h, p["attn_norm"], cfg.norm_eps),
                                 p["attn"], cfg, i, pos)
            attended = attend_seq(q, k, v, cfg, i)
            cache["k"][i] = write_prompt(cache["k"][i], k, n, slot, cfg, i)
            cache["v"][i] = write_prompt(cache["v"][i], v, n, slot, cfg, i)
        h = _close(h, attended, gate, p, i, cfg, valid, routes, loads)
    logits = _head(jax.lax.dynamic_slice_in_dim(h, n - 1, 1, axis=0), params,
                   cfg)
    cache = join(cache, slot, n, logits, routes, temperature, key, units)
    return cache, logits[0], jnp.stack(loads)


def step(params: Params, cache: dict, live, temperature, step_no, *,
         cfg: LagunaConfig, units: UnitIds, seed: int = 0):
    """Every slot advances by one token: the slot's last unit goes in at
    its position through the slot's two kinds of cache, and the next unit
    is sampled.  ``live`` ``[S]`` says which slots hold a row: the others
    are computed (the shape is static) but cost no expert product, count
    for nothing and do not advance.  Returns the cache, the logits ``[S,
    V]`` and the expert layers' load."""
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]))
    pos = cache["pos"]
    h = params["embed"][cache["token"]].astype(F32)
    routes, loads = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("attn_op"):
            q, k, v, gate = qkvg(rms_norm(h, p["attn_norm"], cfg.norm_eps),
                                 p["attn"], cfg, i, pos)
            attended, cache["k"][i], cache["v"][i] = attend_step(
                q, k, v, cache["k"][i], cache["v"][i], pos, cfg, i)
        h = _close(h, attended, gate, p, i, cfg, live, routes, loads)
    logits = _head(h, params, cfg)
    cache = advance(cache, live, logits, routes, temperature, step_no, units,
                    seed)
    return cache, logits, jnp.stack(loads)


def step_admit(params: Params, cache: dict, live, temperature, step_no, ids,
               n, slot, row_temperature, row_key, *, cfg: LagunaConfig,
               units: UnitIds, seed: int = 0):
    """A step that carries an arrival (:func:`~.lfm2.step_admit` says what
    that is): :func:`step` over ``live`` and :func:`prefill` of ``ids``
    ``[T]`` (``n`` real) into ``slot`` in one launch.  Everything row-wise
    runs once over ``[S + T, H]``, the attention's own projections and gate
    among it; between them the slots' rows read their caches and the prompt
    attends over itself.  The prompt's keys and values are written after
    the step's, so they are the last word on ``slot``.  Returns the cache,
    the logits ``[S + 1, V]`` (the slots' rows, then the prompt's last
    position) and the load of both kinds of row together."""
    s, t = live.shape[0], ids.shape[0]
    valid = jnp.concatenate([live, jnp.arange(t) < n])
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]))
    pos = cache["pos"]
    at = jnp.concatenate([pos, jnp.arange(t)])
    h = params["embed"][jnp.concatenate([cache["token"], ids])].astype(F32)
    routes, loads = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("attn_op"):
            q, k, v, gate = qkvg(rms_norm(h, p["attn_norm"], cfg.norm_eps),
                                 p["attn"], cfg, i, at)
            stepped, k_buf, v_buf = attend_step(
                q[:s], k[:s], v[:s], cache["k"][i], cache["v"][i], pos, cfg,
                i)
            attended = jnp.concatenate(
                [stepped, attend_seq(q[s:], k[s:], v[s:], cfg, i)])
            cache["k"][i] = write_prompt(k_buf, k[s:], n, slot, cfg, i)
            cache["v"][i] = write_prompt(v_buf, v[s:], n, slot, cfg, i)
        h = _close(h, attended, gate, p, i, cfg, valid, routes, loads)
    cache, logits = advance_and_join(
        params, cache, h, routes, live, temperature, step_no, n, slot,
        row_temperature, row_key, cfg, units, seed)
    return cache, logits, jnp.stack(loads)


class LagunaBackbone(TokenRows):
    """``laguna``: a row gains a token a step; the programs and what a slot
    holds are its own: keys and values of every position in the full
    layers, a ring of ``window`` places in the window layers."""

    pack_layer = staticmethod(pack_layer)

    def __init__(self, backbone: dict, units: dict, seed: int):
        self.cfg = cfg = LagunaConfig.from_dict(backbone)
        self.units = UnitIds(int(units["first_id"]), int(units["stop_id"]))
        self.layers = len(cfg.layer_types)
        self.seed = seed
        self.held = cfg.held

    def new_cache(self, slots: int, positions: int) -> dict:
        return new_cache(self.cfg, slots, positions)

    def readers(self, positions: int) -> dict:
        """Both geometries: a kind's places (a ring's: the window) and its
        query heads."""
        cfg = self.cfg
        kv = cfg.num_key_value_heads
        return dict(collections.Counter(
            (cfg.places(kind, positions), kv, heads // kv, cfg.head_dim,
             self.block_length)
            for kind, heads in zip(cfg.layer_types, cfg.heads_per_layer)))

    def describe(self, slots: int, positions: int) -> Description:
        """Full layers keep every position of a slot, window layers a ring
        of ``window`` places: the bytes of keys and values a row's step
        reads as held (its positions a full layer, capped at the window a
        ring), whether the band binds it (its position, the last it
        attends over, is at or past the window: the ring has wrapped), and
        the bytes the slots hold in each kind of layer.  A prompt attends
        inside the band in the window layers."""
        base = super().describe(slots, positions)
        cfg = self.cfg
        full, rings = (len(cfg.layers_of(kind)) for kind in (FULL, SLIDING))
        if not rings:
            return base
        window = cfg.sliding_window
        full_bytes, ring_bytes = cfg.place_bytes * full, \
            cfg.place_bytes * rings
        series = 'sonata_attn_cache_resident_bytes{kind="%s"}'
        return dataclasses.replace(
            base, static=dict(base.static, full_layers=full,
                              window_layers=rings, window=window),
            row_sums=base.row_sums + ("kv_cache_bytes",
                                      "window_bound_row_steps"),
            rows=[(*row, full_bytes * n + ring_bytes * min(n, window),
                   int(n > window)) for n, row in enumerate(base.rows)],
            resident={
                series % "full": slots * full_bytes * positions,
                series % "ring": slots * ring_bytes * min(window, positions)},
            prefill=lambda text_bucket: {"window_layers": rings})

    # (70 and 100 MB of code a program without it, 18 and 27 with)
    build_step, build_prefill, build_step_admit = token_step_programs(
        sys.modules[__name__], "laguna", layers_once=True)
