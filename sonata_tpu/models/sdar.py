"""An SDAR-MoE backbone (``model_type: sdar_moe``): units come a block at a
time, by denoising passes over a block that sees itself whole and the
committed blocks before it.

Every layer is ``h += attn(rms(h)); h += moe(rms(h))``: grouped-query
attention with per-head RMS norm on q and k before RoPE, then a
softmax-routed mixture of SwiGLU experts (the best ``num_experts_per_tok``
of a softmax over all experts, renormalised; no shared expert, no dense
layer).  A final RMS norm, then a head of its own.  The pieces are
:mod:`.lfm2`'s; what is here is what a block asks for.

**The mask.**  Position ``i`` sees position ``j`` iff ``j // B <= i // B``
(``B`` the block length; :func:`~.lfm2.block_mask`): whole inside a block,
causal between blocks, for prompt and generated positions alike.

**Generation** (:class:`Schedule`; the family's published loop with
``remasking_strategy: low_confidence_static``).  A row's sequence is its
prompt, then its units; the logits at a position predict the token *at*
that position.  The block being generated starts as mask tokens (the first
one opens with the prompt's last ``n mod B`` ids as known tokens).  A
*denoising* pass runs the block whole over the committed blocks, chooses an
id for every masked position (:func:`~.lfm2.choose`) and unmasks the
``B / denoising_steps`` of them it is surest of (the chosen id's
probability under the distribution it was chosen from); after
``denoising_steps`` of them the block holds no mask token and a *commit*
pass runs it clean: only that pass's keys and values stay in the slot, and
the row moves on to the next block.

Three programs over one set of weights and one cache (:func:`new_cache`):
:func:`prefill` runs one row's prompt and keeps the keys and values of its
whole blocks; :func:`block_pass` runs the current block of every slot, rows
in either phase in one launch, the phase kept on the device (``pass``);
the vocoder reads the row's units from the token row at the prompt's end.
A block takes ``denoising_steps + 1`` passes whatever it holds, so the host
counts a row's launches when it joins and reads nothing back.

**Precision**, as :mod:`.lfm2` states it: weights are bfloat16; every
matrix product takes bfloat16 inputs and accumulates in float32 (the
router's excepted: float32 at ``highest``); the residual stream, RMS norms,
router scores, the softmaxes and the logits are float32; keys and values
are cached in bfloat16, and the softmax's probabilities enter their product
with the values as bfloat16.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.slot_attention import slot_attention, stored_shape, write_rows, \
    write_slot
from .unit_backbone import Backbone, RowPlan, routes_of
from .unit_layers import BF16, F32, UnitIds, _head, _qkv, attn_op_seq, \
    choose, mm, moe_ffn, rms_norm, step_key

Params = dict


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The backbone's published ``config.json`` keys that shape the graph
    (and, constant for the family, what :mod:`.lfm2`'s pieces ask)."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    norm_eps: float
    vocab_size: int
    num_hidden_layers: int
    tie_word_embeddings: bool
    router_scoring: str = "softmax"
    expert_act: str = "swiglu"

    @classmethod
    def from_dict(cls, d: dict) -> "SdarConfig":
        if int(d.get("decoder_sparse_step", 1)) != 1 or d.get(
                "mlp_only_layers") or d.get("attention_bias"):
            raise ValueError("dense layers and attention biases are not "
                             "supported")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            rope_theta=float(d["rope_theta"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            norm_eps=float(d["rms_norm_eps"]),
            vocab_size=int(d["vocab_size"]),
            num_hidden_layers=int(d["num_hidden_layers"]),
            tie_word_embeddings=bool(d["tie_word_embeddings"]))

    @property
    def expert_layers(self) -> list:
        return list(range(self.num_hidden_layers))


@dataclasses.dataclass(frozen=True)
class Schedule:
    """How a block is generated: ``block_length`` positions, by
    ``denoising_steps`` denoising passes and a commit pass; ``mask_id``
    stands for a position not yet decided."""

    block_length: int
    denoising_steps: int
    mask_id: int

    def __post_init__(self):
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(f"{self.denoising_steps} denoising passes do "
                             f"not fill a block of {self.block_length}")

    @property
    def passes(self) -> int:
        """Launches a block takes."""
        return self.denoising_steps + 1

    @property
    def transfers(self) -> tuple:
        """Positions each denoising pass unmasks (the remainder of an
        uneven split goes to the first passes, as published)."""
        base, more = divmod(self.block_length, self.denoising_steps)
        return tuple(base + (k < more) for k in range(self.denoising_steps))


def pack_layer(raw: dict) -> Params:
    """One layer from its tensors under the reference's names to the layout
    :mod:`.lfm2`'s pieces read (``wq | wk | wv`` and ``w1 | w3`` side by
    side); norms and the router float32."""
    attn, moe = raw["attn"], raw["moe"]
    return {"in_norm": raw["in_norm"].astype(F32),
            "post_norm": raw["post_norm"].astype(F32),
            "attn": {"wqkv": jnp.concatenate(
                         [attn["wq"], attn["wk"], attn["wv"]], -1),
                     "wo": attn["wo"], "q_norm": attn["q_norm"].astype(F32),
                     "k_norm": attn["k_norm"].astype(F32)},
            "moe": {"router": moe["router"].astype(F32),
                    "w13": jnp.concatenate([moe["w1"], moe["w3"]], -1),
                    "w2": moe["w2"]}}


def attn_op_block(u, p, cfg: SdarConfig, k_buf, v_buf, pos):
    """The block ``pos`` ``[S, B]`` of every slot (``u`` ``[S * B, H]``),
    whole, over the slots' keys and values before it
    (``ops/slot_attention.py`` says where they lie).  The block's own keys
    and values take their places in the slot: those of the last pass over
    a block are the ones that stay."""
    with jax.named_scope("attn_op"):
        s, b = pos.shape
        kv, d = cfg.num_key_value_heads, cfg.head_dim
        g = cfg.num_attention_heads // kv
        q, k, v = _qkv(u, p, cfg, pos.reshape(-1))
        k_buf = write_rows(k_buf, k.reshape(s, b, kv, d), pos)
        v_buf = write_rows(v_buf, v.reshape(s, b, kv, d), pos)
        out = slot_attention(q.reshape(s, b, kv, g, d), k_buf, v_buf,
                             pos[:, 0] + b)
        return mm(out.reshape(s * b, -1), p["wo"]), k_buf, v_buf


def _moe_half(h, p, cfg: SdarConfig, held, valid, routes: list, loads: list):
    out, chosen, load = moe_ffn(rms_norm(h, p["post_norm"], cfg.norm_eps),
                                p["moe"], cfg, held, valid)
    routes.append(chosen)
    loads.append(load)
    return h + out


def unmask(logits, x, temperature, key, pass_no, units: UnitIds,
           schedule: Schedule):
    """One denoising pass's choice for blocks ``x`` ``[S, B]`` with logits
    ``[S * B, V]``, as the head left them: an id for every masked position
    and, out of the same reading of the logits, its log-probability
    (:func:`~.lfm2.choose`); the ``schedule.transfers[pass_no]`` surest of
    them are unmasked.  Returns the blocks and which positions were
    unmasked."""
    with jax.named_scope("unmask"):
        s, b = x.shape
        chosen, confidence = choose(logits, jnp.repeat(temperature, b), key,
                                    units)
        masked = x == schedule.mask_id
        confidence = jnp.where(masked, confidence.reshape(s, b), -jnp.inf)
        # a position's rank among its block's, the surest first
        order = jnp.argsort(-confidence, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        count = jnp.asarray(schedule.transfers + (0,), jnp.int32)[pass_no]
        taken = masked & (rank < count[:, None])
        return jnp.where(taken, chosen.reshape(s, b), x), taken


# ---------------------------------------------------------------------------
# the generation state and the two programs
# ---------------------------------------------------------------------------

def new_cache(cfg: SdarConfig, slots: int, positions: int) -> dict:
    """The state of ``slots`` rows of at most ``positions`` tokens: keys
    and values of every layer; per slot the token row (a position still
    masked holds the mask id), the current block's first position and the
    pass number inside it; the pass at which every position was unmasked
    (-1: given, or not yet), and the experts every token chose.  Keys,
    values and the experts chosen lie as
    :func:`~sonata_tpu.ops.slot_attention.stored_shape` says."""
    kv_shape = stored_shape(slots, positions, cfg.num_key_value_heads,
                            cfg.head_dim)
    n = cfg.num_hidden_layers
    return {
        "k": [jnp.zeros(kv_shape, BF16) for _ in range(n)],
        "v": [jnp.zeros(kv_shape, BF16) for _ in range(n)],
        "tokens": jnp.zeros((slots, positions), jnp.int32),
        "start": jnp.zeros((slots,), jnp.int32),
        "pass": jnp.zeros((slots,), jnp.int32),
        "unmasked_at": jnp.full((slots, positions), -1, jnp.int8),
        "routes": jnp.zeros(stored_shape(
            slots, positions, n, cfg.num_experts_per_tok), jnp.int8),
    }


def prefill(params: Params, cache: dict, ids, n, slot, *, cfg: SdarConfig,
            schedule: Schedule, held=None):
    """One row joins: its prompt ``ids`` ``[T]`` (``n`` real) runs under
    the mask; the keys and values of its ``n // B`` whole blocks go into
    ``slot``, and the last ``n mod B`` ids open the first generated block
    beside mask tokens.  No logits are needed and none are computed.
    Returns the cache and the expert layers' load ``[layers, 3]``."""
    t, b = ids.shape[0], schedule.block_length
    span = cache["tokens"].shape[1]
    whole = n // b * b
    # only the whole blocks' positions are kept: the others (the tail, the
    # padding) cost no expert product
    valid = jnp.arange(t) < whole
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]))
    h = params["embed"][ids].astype(F32)
    routes, loads = [], []
    for i in range(cfg.num_hidden_layers):
        p = params["layers"][i]
        op, k, v = attn_op_seq(rms_norm(h, p["in_norm"], cfg.norm_eps),
                               p["attn"], cfg, b)
        cache["k"][i] = write_slot(cache["k"][i], k, slot)
        cache["v"][i] = write_slot(cache["v"][i], v, slot)
        h = _moe_half(h + op, p, cfg, held, valid, routes, loads)
    at = jnp.arange(span)
    row = jnp.zeros((span,), jnp.int32).at[:t].set(ids)
    cache["tokens"] = cache["tokens"].at[slot].set(
        jnp.where(at < n, row, schedule.mask_id))
    cache["start"] = cache["start"].at[slot].set(whole)
    cache["pass"] = cache["pass"].at[slot].set(0)
    cache["unmasked_at"] = cache["unmasked_at"].at[slot].set(-1)
    cache["routes"] = write_slot(cache["routes"], jnp.stack(routes, 1), slot)
    return cache, jnp.stack(loads)


def block_pass(params: Params, cache: dict, live, temperature, step_no, *,
               cfg: SdarConfig, schedule: Schedule, units: UnitIds,
               seed: int = 0, held=None):
    """Every slot's current block takes one pass: a denoising pass while
    the slot's ``pass`` is below ``denoising_steps``, else the commit pass,
    after which the slot stands at its next block.  ``live`` ``[S]`` says
    which slots hold a row: the others are computed (the shape is static)
    but cost no expert product, count for nothing and do not move.
    Returns the cache, what the pass saw and gave (the blocks as they went
    in ``[S, B]``, the logits ``[S * B, V]``, a slot's ``B`` rows after
    each other as the head left them, the experts chosen ``[S, B, layers,
    k]``) and the expert layers' load ``[layers, 3]``."""
    b = schedule.block_length
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]))
    s, span = cache["tokens"].shape
    rows = jnp.arange(s)[:, None]
    pos = cache["start"][:, None] + jnp.arange(b)[None, :]
    x = cache["tokens"][rows, pos]
    valid = jnp.repeat(live, b)
    h = params["embed"][x.reshape(-1)].astype(F32)
    routes, loads = [], []
    for i in range(cfg.num_hidden_layers):
        p = params["layers"][i]
        op, cache["k"][i], cache["v"][i] = attn_op_block(
            rms_norm(h, p["in_norm"], cfg.norm_eps), p["attn"], cfg,
            cache["k"][i], cache["v"][i], pos)
        h = _moe_half(h + op, p, cfg, held, valid, routes, loads)
    logits = _head(h, params, cfg)
    chose = jnp.stack(routes, 1).astype(jnp.int8).reshape(s, b, len(routes),
                                                          -1)
    pass_no = cache["pass"]
    commit = pass_no >= schedule.denoising_steps
    after, taken = unmask(logits, x, temperature, step_key(seed, step_no),
                          pass_no, units, schedule)
    denoise = (live & ~commit)[:, None]
    cache["tokens"] = cache["tokens"].at[rows, pos].set(
        jnp.where(denoise, after, x))
    cache["unmasked_at"] = cache["unmasked_at"].at[rows, pos].set(jnp.where(
        denoise & taken, pass_no[:, None].astype(jnp.int8),
        cache["unmasked_at"][rows, pos]))
    cache["routes"] = write_rows(cache["routes"], chose, pos)
    # an empty slot stays where it is, and no slot leaves the cache
    cache["start"] = jnp.where(live & commit, jnp.minimum(
        cache["start"] + b, span - b), cache["start"])
    cache["pass"] = jnp.where(live, jnp.where(commit, 0, pass_no + 1),
                              pass_no)
    return cache, (x, logits, chose), jnp.stack(loads)


class SdarBackbone(Backbone):
    """``sdar_moe``: the prefill keeps the prompt's whole blocks, and every
    ``denoising_steps + 1`` passes give every live row a block of units."""

    pack_layer = staticmethod(pack_layer)
    #: no step of this backbone carries an arrival (a pass keeps a phase a
    #: slot): a row's prompt runs apart, in ``sdar_prefill``
    build_step_admit = None
    #: a flagged row's logits are ``[B, V]`` a pass: every sixteenth block
    DUMP_EVERY = 16

    def __init__(self, backbone: dict, units: dict, seed: int):
        self.cfg = SdarConfig.from_dict(backbone)
        self.units = UnitIds(int(units["first_id"]), int(units["stop_id"]),
                             int(units["mask_id"]))
        self.schedule = Schedule(
            int(units["block_length"]),
            int(units.get("denoising_steps", 4)), self.units.mask_id)
        self.block_length = self.schedule.block_length
        self.denoising_steps = self.schedule.denoising_steps
        self.layers = self.attention_layers = self.cfg.num_hidden_layers
        self.seed = seed

    def new_cache(self, slots: int, positions: int) -> dict:
        return new_cache(self.cfg, slots, positions)

    def _blocks(self, n_ids: int, budget: int) -> int:
        """Blocks a row generates: the first one opens with the prompt's
        last ``n mod B`` ids, the last one may run past the budget."""
        b = self.block_length
        return -(-(n_ids % b + budget) // b)

    def positions_needed(self, n_ids: int, budget: int) -> int:
        b = self.block_length
        return n_ids // b * b + self._blocks(n_ids, budget) * b

    def plan(self, n_ids: int, budget: int) -> RowPlan:
        b = self.block_length
        return RowPlan(
            launches=self._blocks(n_ids, budget) * self.schedule.passes,
            budget=budget, block=b, passes=self.schedule.passes,
            first_units=-(n_ids % b), first_attended=n_ids // b * b + b)

    def dumped(self, plan: RowPlan, done: int) -> bool:
        """Whether a flagged row keeps what launch number ``done`` gave:
        every pass of its first block, of its last, and of every
        :data:`DUMP_EVERY`-th between."""
        block = done // plan.passes
        return block % self.DUMP_EVERY == 0 \
            or block == (plan.launches - 1) // plan.passes

    def build_step(self):
        cfg, schedule, units, seed = (self.cfg, self.schedule, self.units,
                                      self.seed)

        def sdar_pass(params, cache, live, temperature, step_no):
            return block_pass(params, cache, live, temperature, step_no,
                                   cfg=cfg, schedule=schedule, units=units,
                                   seed=seed)

        return jax.jit(sdar_pass, donate_argnums=(1,))

    def build_prefill(self):
        cfg, schedule = self.cfg, self.schedule

        def sdar_prefill(params, cache, ids, n, slot, temperature, row_no):
            cache, load = prefill(params, cache, ids, n, slot, cfg=cfg,
                                       schedule=schedule)
            return cache, None, load

        return jax.jit(sdar_prefill, donate_argnums=(1,))

    def units_of(self, cache, n_ids: int) -> tuple:
        return cache["tokens"], n_ids

    def take(self, kept: tuple, rows) -> tuple:
        """A pass leaves its logits ``[S * B, V]`` as the head wrote them:
        a slot's are the ``B`` rows from ``slot * B`` on, cut here to the
        ``[B, V]`` a flagged row keeps."""
        x, logits, chose = kept
        b = self.block_length
        return (x[rows], logits[rows[:, None] * b + jnp.arange(b)],
                chose[rows])

    def record(self, cache, slot: int) -> tuple:
        return (cache["tokens"][slot], cache["routes"][slot],
                cache["unmasked_at"][slot])

    def dump(self, ids: list, budget: int, kept: list, record) -> dict:
        """The row's tokens as committed (prompt, units, the last block's
        surplus), the experts every position chose in its commit pass, the
        pass at which every position was unmasked, and for the launches of
        ``passes`` the block as it went in, its float32 logits ``[B, V]``
        and the experts it chose."""
        tokens, routes, unmasked_at = record
        t = self.positions_needed(len(ids), budget)
        return {"tokens": tokens[:t],
                "routes": routes_of(self.cfg, routes)[:t],
                "unmasked_at": unmasked_at[:t],
                "passes": np.asarray([d for d, _ in kept], np.int32),
                "seen": np.stack([a[0] for _, a in kept]),
                "logits": np.stack([a[1] for _, a in kept]),
                "pass_routes": np.stack([a[2] for _, a in kept]),
                "block_length": np.int32(self.block_length),
                "denoising_steps": np.int32(self.denoising_steps)}
