"""An openPangu-Ultra-MoE backbone (``model_type: pangu_ultra_moe``) for
step-wise generation: latent attention (MLA) over one cache row a position,
four norms a layer, and an expert layer of whose routed experts, like the
vocabulary's rows, this chip may hold a thin share.

Every layer, on the residual ``h`` (``sandwich_norm``: a norm before *and*
after each sublayer)::

    h = h + rms(attn(rms(h; input_layernorm)); post_attention_layernorm)
    h = h + rms(ffn(rms(h; pre_mlp_layernorm)); post_mlp_layernorm)

``ffn`` is a dense SwiGLU in the first ``first_k_dense_replace`` layers and
after them the expert layer: :func:`~.lfm2.route`'s sigmoid router with no
bias (the ``num_experts_per_tok`` largest of ``n_routed_experts`` scores,
renormalised, times ``routed_scaling_factor``), SwiGLU experts, and a
shared expert every token takes.  :func:`~.lfm2.moe_ffn` runs it; ``held =
(first, count)`` says which of the router's experts this chip holds (the
configuration's ``expert_parallel`` block), and what the others would add
is left out.  A final RMS norm, then a head of its own over the
vocabulary's rows held here (``vocab_parallel``: this chip's slice starts
at id 0, so an id is its row).

**Latent attention.**  ``u = rms(h)``; ``c_q = rms(u W_qa)``, ``q = c_q
W_qb``: per head ``[q_nope | q_rope]``; ``[c_kv | k_r] = u W_kva``, ``c_kv =
rms(c_kv)``; rotary (by halves, plain) on every head's ``q_rope`` and on the
one ``k_r`` all heads share; ``[k_nope_h | v_h] = c_kv W_kvb,h``; scores
``(q_nope_h . k_nope_h + q_rope_h . k_r) / sqrt(nope + rope)``, causal; ``o
= concat_h(sum p v_h) W_o``.  Two forms that agree:

- :func:`mla_seq`, **expanded**: a prompt makes every head's keys and
  values of its positions' ``c_kv`` and attends per head;
- :func:`mla_step`, **absorbed**: a step folds ``W_kvb``'s key half into
  the query, ``q~_h = q_nope_h W_kvb,K,h^T``, attends over the cached rows
  themselves (scores ``[q~_h | q_rope_h] . [c_kv | k_r]``, ``o~_h = sum p
  c_kv``) and unfolds ``o_h = o~_h W_kvb,V,h``: no key or value of any head
  is ever stored or rebuilt.

**State a slot holds** (:func:`new_cache`): per layer one row a position,
``[c_kv | k_r]`` after its norm and rotary, bfloat16, in whole lanes (576
values in 640: ``ops/slot_attention.py``): keys and values at once, one
buffer a layer, read once a step.  Either form writes the same rows.

**Not served:** the multi-token prediction module
(``num_nextn_predict_layers``): the published forward pass does not run it
either, so the units are the model's without it.

**Precision**, as :mod:`.lfm2` states it, and: all six kinds of norm
(``c_q``'s and ``c_kv``'s among them) and rotary are float32; the latent row
is cached in bfloat16 and the probabilities enter their product with it as
bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.slot_attention import latent_attention, latent_implementation, \
    latent_places, latent_reach, stored_shape, write_rows, write_slot
from .unit_backbone import Description, TokenRows, token_step_programs
from .unit_layers import BF16, F32, UnitIds, _head, advance, \
    advance_and_join, apply_rope, block_mask, dense_ffn, join, mm, moe_ffn, \
    rms_norm

Params = dict


@dataclasses.dataclass(frozen=True)
class PanguConfig:
    """The backbone's published ``config.json`` keys that shape the graph
    (and, constant for the family, what :mod:`.lfm2`'s pieces ask)."""

    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int            #: the router's width
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    norm_eps: float
    vocab_size: int             #: the vocabulary's rows held here
    #: the routed experts this chip holds: ``(first, count)``
    held: tuple
    tie_word_embeddings: bool = False
    router_scoring: str = "sigmoid"
    use_expert_bias: bool = False
    expert_act: str = "swiglu"

    @classmethod
    def from_dict(cls, d: dict) -> "PanguConfig":
        if not d.get("sandwich_norm") or d.get("attention_bias") or d.get(
                "hidden_act") != "silu" or int(
                d.get("n_shared_experts", 1)) != 1 or int(
                d["num_key_value_heads"]) != int(d["num_attention_heads"]):
            raise ValueError("only sandwich norms, SiLU, one shared expert, "
                             "as many key-value heads as heads and no "
                             "attention bias are supported")
        layers, dense = (int(d["num_hidden_layers"]),
                         int(d["first_k_dense_replace"]))
        if not 0 <= dense <= layers:
            raise ValueError(f"first_k_dense_replace {dense} of {layers} "
                             "layers")
        held_here = int(d["n_routed_experts"])
        share = d.get("expert_parallel") or {
            "routed_experts": held_here, "held": [0, held_here]}
        held = tuple(int(v) for v in share["held"])
        if held[1] != held_here or held[0] < 0 or sum(held) > int(
                share["routed_experts"]):
            raise ValueError(f"held = {held} is not n_routed_experts = "
                             f"{held_here} of {share['routed_experts']}")
        vocab = int(d["vocab_size"])
        rows = d.get("vocab_parallel") or {"vocab_size": vocab,
                                           "held": [0, vocab]}
        if [int(v) for v in rows["held"]] != [0, vocab] or vocab > int(
                rows["vocab_size"]):
            # a slice that starts elsewhere would need the other chips'
            # embedding rows for the ids it is fed
            raise ValueError(f"vocab_parallel.held = {rows['held']} is not "
                             f"the first vocab_size = {vocab} ids")
        return cls(
            hidden_size=int(d["hidden_size"]), num_hidden_layers=layers,
            first_k_dense_replace=dense,
            num_attention_heads=int(d["num_attention_heads"]),
            q_lora_rank=int(d["q_lora_rank"]),
            kv_lora_rank=int(d["kv_lora_rank"]),
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=int(d["qk_rope_head_dim"]),
            v_head_dim=int(d["v_head_dim"]),
            rope_theta=float(d["rope_theta"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(share["routed_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            norm_eps=float(d["rms_norm_eps"]), vocab_size=vocab, held=held,
            tie_word_embeddings=bool(d["tie_word_embeddings"]))

    @property
    def expert_layers(self) -> list:
        return list(range(self.first_k_dense_replace, self.num_hidden_layers))

    @property
    def latent_width(self) -> int:
        """Values of a cached row: ``c_kv | k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def latent_cache_bytes(self, positions: int) -> int:
        """Bytes of ``positions`` cached rows over all layers, as stored."""
        return 2 * self.num_hidden_layers * positions * stored_shape(
            1, 1, 1, self.latent_width)[-1]


def pack_layer(raw: dict, cfg: PanguConfig) -> Params:
    """One layer from its tensors under the reference's names (bfloat16) to
    the layout the programs read: ``W_qa | W_kva`` side by side (one product
    of ``u``), ``W_kvb`` ``[c, heads x (nope + v)]`` as its key half and its
    value half, each ``[heads, c, d]``; the six norms and the router
    float32; ``w1 | w3`` side by side as ``w13`` (the shared expert's as
    ``w_up``)."""
    attn, ffn = raw["attn"], raw["ffn"]
    per_head = attn["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim).transpose(1, 0, 2)
    op = {"wqkv_a": jnp.concatenate([attn["wq_a"], attn["wkv_a"]], -1),
          "wq_b": attn["wq_b"],
          "wk_b": per_head[..., :cfg.qk_nope_head_dim],
          "wv_b": per_head[..., cfg.qk_nope_head_dim:], "wo": attn["wo"],
          "q_norm": attn["q_norm"].astype(F32),
          "kv_norm": attn["kv_norm"].astype(F32)}
    ffn_p = {"w13": jnp.concatenate([ffn["w1"], ffn["w3"]], -1),
             "w2": ffn["w2"]}
    if "router" in ffn:
        ffn_p.update(
            router=ffn["router"].astype(F32),
            shared={"w_up": jnp.concatenate([ffn["shared_w1"],
                                             ffn["shared_w3"]], -1),
                    "w_down": ffn["shared_w2"]})
    norms = {k: raw[k].astype(F32) for k in (
        "input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")}
    return dict(norms, attn=op, ffn=ffn_p)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def mla_in(u, p, cfg: PanguConfig, positions, rotary=None):
    """What both forms share, row-wise over ``u`` ``[N, H]`` at
    ``positions`` ``[N]``: every head's ``q_nope`` ``[N, heads, nope]`` and
    rotated ``q_rope`` ``[N, heads, rope]`` (float32), and the row to cache,
    ``[c_kv | k_r]`` ``[N, 1, c + rope]`` after its norm and rotary, in the
    cache's type.  ``rotary(x, positions)`` is the rotary of a backbone
    that brings its own rule (:mod:`.gigachat`: YaRN's paces); None: plain,
    by halves, at ``cfg.rope_theta``."""
    if rotary is None:
        def rotary(x, at):
            return apply_rope(x, at, cfg.rope_theta)
    n, heads = u.shape[0], cfg.num_attention_heads
    nope, rope, c = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.kv_lora_rank)
    down = mm(u, p["wqkv_a"])
    c_q = rms_norm(down[:, :cfg.q_lora_rank], p["q_norm"], cfg.norm_eps)
    q = mm(c_q, p["wq_b"]).reshape(n, heads, nope + rope)
    q_rope = rotary(q[..., nope:], positions)
    c_kv = rms_norm(down[:, cfg.q_lora_rank:cfg.q_lora_rank + c],
                    p["kv_norm"], cfg.norm_eps)
    k_r = rotary(down[:, None, cfg.q_lora_rank + c:], positions)
    row = jnp.concatenate([c_kv[:, None], k_r], -1).astype(BF16)
    return q[..., :nope], q_rope, row


def mla_seq(q_nope, q_rope, row, p, cfg: PanguConfig):
    """The expanded form over a row's prompt whole (``[T, ...]`` of
    :func:`mla_in`): every head's keys and values from the positions'
    ``c_kv``, causal.  Returns ``[T, heads x v]`` before ``W_o``."""
    t, c = row.shape[0], cfg.kv_lora_rank
    c_kv, k_r = row[:, 0, :c], row[:, 0, c:]
    k_nope = jnp.einsum("pc,hcd->phd", c_kv, p["wk_b"],
                        preferred_element_type=F32).astype(BF16)
    v = jnp.einsum("pc,hcd->phd", c_kv, p["wv_b"],
                   preferred_element_type=F32).astype(BF16)
    scores = (jnp.einsum("qhd,phd->hqp", q_nope.astype(BF16), k_nope,
                         preferred_element_type=F32)
              + jnp.einsum("qhd,pd->hqp", q_rope.astype(BF16), k_r,
                           preferred_element_type=F32)) * cfg.softmax_scale
    probs = jax.nn.softmax(
        jnp.where(block_mask(jnp.arange(t)), scores, -jnp.inf), -1)
    out = jnp.einsum("hqp,phd->qhd", probs.astype(BF16), v,
                     preferred_element_type=F32)
    return out.reshape(t, -1)


def mla_step(q_nope, q_rope, buf, upto, p, cfg: PanguConfig):
    """The absorbed form: one token of every slot (``[S, ...]`` of
    :func:`mla_in`) over the slots' cached rows ``buf`` at the places ``<
    upto``.  Returns ``[S, heads x v]`` before ``W_o``."""
    s = q_nope.shape[0]
    folded = jnp.einsum("shd,hcd->shc", q_nope.astype(BF16), p["wk_b"],
                        preferred_element_type=F32)
    q = jnp.concatenate([folded, q_rope], -1)[:, None]
    latent = latent_attention(q, buf, upto, cfg.kv_lora_rank,
                              cfg.softmax_scale)[:, 0]
    out = jnp.einsum("shc,hcd->hsd", latent.astype(BF16), p["wv_b"],
                     preferred_element_type=F32)
    return out.transpose(1, 0, 2).reshape(s, -1)


def _ffn(u, p, i: int, cfg: PanguConfig, valid, routes: list, loads: list):
    """Layer ``i``'s feed-forward of ``u``; an expert layer appends the
    experts chosen and its load."""
    if i < cfg.first_k_dense_replace:
        return dense_ffn(u, p["ffn"])
    out, chosen, load = moe_ffn(u, p["ffn"], cfg, cfg.held, valid)
    routes.append(chosen)
    loads.append(load)
    return out


def _close(h, attended, p, i: int, cfg: PanguConfig, valid, routes, loads):
    """The layer from behind its attention's core: ``W_o``, the norm after
    the sublayer, and the feed-forward between its two norms."""
    eps = cfg.norm_eps
    with jax.named_scope("mla_op"):
        a = mm(attended, p["attn"]["wo"])
    h = h + rms_norm(a, p["post_attn_norm"], eps)
    m = _ffn(rms_norm(h, p["pre_mlp_norm"], eps), p, i, cfg, valid, routes,
             loads)
    return h + rms_norm(m, p["post_mlp_norm"], eps)


# ---------------------------------------------------------------------------
# the generation state and the programs
# ---------------------------------------------------------------------------

def new_cache(cfg: PanguConfig, slots: int, positions: int) -> dict:
    """The state of ``slots`` rows of at most ``positions`` tokens: per
    layer the latent rows, one a position (keys and values at once), and
    per slot the next token, its position, the units sampled so far and
    the experts every token chose (unsigned bytes: the router has 256
    outputs).  Rows lie as
    :func:`~sonata_tpu.ops.slot_attention.stored_shape` says."""
    row = stored_shape(slots, positions, 1, cfg.latent_width)
    return {
        "latent": [jnp.zeros(row, BF16)
                   for _ in range(cfg.num_hidden_layers)],
        "token": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "count": jnp.zeros((slots,), jnp.int32),
        "units": jnp.zeros((slots, positions), jnp.int32),
        "routes": jnp.zeros(stored_shape(
            slots, positions, len(cfg.expert_layers),
            cfg.num_experts_per_tok), jnp.uint8),
    }


def prefill(params: Params, cache: dict, ids, n, slot, temperature, key, *,
            cfg: PanguConfig, units: UnitIds):
    """One row joins: its prompt ``ids`` ``[T]`` (``n`` real) runs whole in
    the expanded form, its latent rows go into ``slot`` and its first unit
    is sampled from the logits at the prompt's last position.  Returns the
    cache, those logits ``[V]`` and the expert layers' load."""
    t = ids.shape[0]
    valid = jnp.arange(t) < n
    cache = dict(cache, latent=list(cache["latent"]))
    h = params["embed"][ids].astype(F32)
    pos = jnp.arange(t)
    routes, loads = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("mla_op"):
            q_nope, q_rope, row = mla_in(
                rms_norm(h, p["input_norm"], cfg.norm_eps), p["attn"], cfg,
                pos)
            attended = mla_seq(q_nope, q_rope, row, p["attn"], cfg)
            cache["latent"][i] = write_slot(cache["latent"][i], row, slot)
        h = _close(h, attended, p, i, cfg, valid, routes, loads)
    logits = _head(lax.dynamic_slice_in_dim(h, n - 1, 1, axis=0), params,
                   cfg)
    cache = join(cache, slot, n, logits, routes, temperature, key, units)
    return cache, logits[0], jnp.stack(loads)


def step(params: Params, cache: dict, live, temperature, step_no, *,
         cfg: PanguConfig, units: UnitIds, seed: int = 0):
    """Every slot advances by one token in the absorbed form: the slot's
    last unit goes in at its position, its latent row is written, and the
    next unit is sampled.  ``live`` ``[S]`` says which slots hold a row:
    the others are computed (the shape is static) but cost no expert
    product, count for nothing and do not advance.  Returns the cache, the
    logits ``[S, V]`` and the expert layers' load."""
    cache = dict(cache, latent=list(cache["latent"]))
    pos = cache["pos"]
    h = params["embed"][cache["token"]].astype(F32)
    routes, loads = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("mla_op"):
            q_nope, q_rope, row = mla_in(
                rms_norm(h, p["input_norm"], cfg.norm_eps), p["attn"], cfg,
                pos)
            cache["latent"][i] = write_rows(cache["latent"][i], row[:, None],
                                            pos[:, None])
            attended = mla_step(q_nope, q_rope, cache["latent"][i], pos + 1,
                                p["attn"], cfg)
        h = _close(h, attended, p, i, cfg, live, routes, loads)
    logits = _head(h, params, cfg)
    cache = advance(cache, live, logits, routes, temperature, step_no, units,
                    seed)
    return cache, logits, jnp.stack(loads)


def step_admit(params: Params, cache: dict, live, temperature, step_no, ids,
               n, slot, row_temperature, row_key, *, cfg: PanguConfig,
               units: UnitIds, seed: int = 0):
    """A step that carries an arrival (:func:`~.lfm2.step_admit` says what
    that is): :func:`step` over ``live`` and :func:`prefill` of ``ids``
    ``[T]`` (``n`` real) into ``slot`` in one launch.  Everything row-wise
    runs once over ``[S + T, H]``, the attention's own projections among it
    (``W_qa | W_kva``, ``W_qb`` and ``W_o``: four fifths of a layer's
    attention weights, read once); between them the slots' rows take the
    absorbed form over the cache and the prompt the expanded form over
    itself.  The prompt's rows are written after the step's, so they are
    the last word on ``slot``.  Returns the cache, the logits ``[S + 1, V]``
    (the slots' rows, then the prompt's last position) and the load of both
    kinds of row together."""
    s, t = live.shape[0], ids.shape[0]
    valid = jnp.concatenate([live, jnp.arange(t) < n])
    cache = dict(cache, latent=list(cache["latent"]))
    pos = cache["pos"]
    at = jnp.concatenate([pos, jnp.arange(t)])
    h = params["embed"][jnp.concatenate([cache["token"], ids])].astype(F32)
    routes, loads = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("mla_op"):
            q_nope, q_rope, row = mla_in(
                rms_norm(h, p["input_norm"], cfg.norm_eps), p["attn"], cfg,
                at)
            buf = write_rows(cache["latent"][i], row[:s, None], pos[:, None])
            attended = jnp.concatenate([
                mla_step(q_nope[:s], q_rope[:s], buf, pos + 1, p["attn"],
                         cfg),
                mla_seq(q_nope[s:], q_rope[s:], row[s:], p["attn"], cfg)])
            cache["latent"][i] = write_slot(buf, row[s:], slot)
        h = _close(h, attended, p, i, cfg, valid, routes, loads)
    cache, logits = advance_and_join(
        params, cache, h, routes, live, temperature, step_no, n, slot,
        row_temperature, row_key, cfg, units, seed)
    return cache, logits, jnp.stack(loads)


class PanguBackbone(TokenRows):
    """``pangu_ultra_moe``: a row gains a token a step; the programs and
    what a slot holds (one latent row a position and layer) are its own.  A
    step runs latent attention's absorbed form, a prompt (apart or carried)
    the expanded one."""

    def __init__(self, backbone: dict, units: dict, seed: int):
        self.cfg = PanguConfig.from_dict(backbone)
        self.units = UnitIds(int(units["first_id"]), int(units["stop_id"]))
        self.layers = self.cfg.num_hidden_layers
        self.seed = seed
        self.held = self.cfg.held
        self.pack_layer = functools.partial(pack_layer, cfg=self.cfg)

    def new_cache(self, slots: int, positions: int) -> dict:
        return new_cache(self.cfg, slots, positions)

    def attention(self, positions: int) -> str:
        cfg = self.cfg
        return latent_implementation(
            positions, cfg.num_attention_heads, cfg.latent_width,
            cfg.kv_lora_rank, self.block_length)

    def latent_chunk(self, positions: int) -> int:
        """What the step's latent reader rounds a row's places up to
        (``slot_attention.latent_places``' chunk)."""
        cfg = self.cfg
        return latent_reach(
            positions, cfg.num_attention_heads, cfg.latent_width,
            cfg.kv_lora_rank, self.block_length)

    def describe(self, slots: int, positions: int) -> Description:
        """Every layer's cache is one latent row a position: the places a
        layer's latent reader moves for a row of each length (whole chunks
        of the kernel's, every position where the einsum reads), the bytes
        of the rows the live rows' attention read, as stored, and of those
        the slots hold.  A step runs the absorbed form; a prompt makes
        every head's keys and values of its own rows."""
        base = super().describe(slots, positions)
        chunk, row_bytes = self.latent_chunk(positions), \
            self.cfg.latent_cache_bytes
        return dataclasses.replace(
            base, static=dict(base.static, latent_layers=self.layers,
                              mla_form="absorbed"),
            rows=[(latent_places(n, chunk), kv)
                  for n, (_, kv) in enumerate(base.rows)],
            closed=lambda g: dict(base.closed(g), latent_cache_bytes=(
                row_bytes(g["kv_positions"]))),
            resident={"sonata_mla_cache_resident_bytes":
                      row_bytes(slots * positions)},
            prefill=lambda text_bucket: {"mla_form": "expanded"})

    build_step, build_prefill, build_step_admit = token_step_programs(
        sys.modules[__name__], "pangu")
