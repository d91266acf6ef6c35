"""An LFM2-MoE backbone (``model_type: lfm2_moe``) for step-wise generation.

Every layer is ``h += op(rms(h)); h += ffn(rms(h))``: ``op`` is a gated
short convolution (``conv`` layers, state: the last ``conv_L_cache`` columns
of ``B * x``) or grouped-query attention with per-head RMS norm on q and k
before RoPE (``full_attention`` layers, state: keys and values); ``ffn`` is a
dense SwiGLU in the first ``num_dense_layers`` layers and a sigmoid-routed
mixture of SwiGLU experts in the others.  A final RMS norm, then the head,
tied to the embedding.  The pieces every backbone of a unit voice is made
of (the attention mixer, the expert layer, the head, the sampler, a slot's
tail) are :mod:`.unit_layers`'; here are the configuration, the weights'
layout, the convolution mixer, the cache and the programs.

Three programs over one set of weights: :func:`prefill` runs one row's
prompt whole, writes its state into a slot of the cache and samples the
row's first token; :func:`step` advances every slot by one token;
:func:`step_admit` does both in one launch (the live rows step, an arriving
row's prompt rides beside them: each kind of row through its own mixer,
everything row-wise, the expert layer above all, once over both, so that a
touched expert leaves HBM once).  All keep the generation state on the
device (:func:`new_cache`): the host says which slots are live and reads
nothing back to decide the next launch.

**Precision**, as :mod:`.unit_layers` states it, and: the depthwise
convolution and its state are float32.
"""

from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.slot_attention import stored_shape, write_slot
from .unit_backbone import TokenRows, token_step_programs
from .unit_layers import BF16, F32, Params, UnitIds, _head, advance, \
    advance_and_join, attn_op_seq, attn_op_step, dense_ffn, join, mm, \
    moe_ffn, rms_norm



@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The backbone's published ``config.json`` keys that shape the graph."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    rope_theta: float
    conv_L_cache: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    use_expert_bias: bool
    routed_scaling_factor: float
    num_dense_layers: int
    norm_eps: float
    vocab_size: int
    layer_types: tuple
    head_dim: int
    #: how the router scores: ``sigmoid`` (+ ``expert_bias`` for the
    #: selection) or ``softmax`` over all experts
    router_scoring: str = "sigmoid"
    #: false: the head is a matrix of its own (``params["head"]``)
    tie_word_embeddings: bool = True
    #: an expert's form: ``swiglu`` (``(silu(u w1) * (u w3)) w2``, ``w13``
    #: the two side by side) or ``relu2`` (``relu(u w_up)^2 w_down``, no
    #: gate: ``w13`` is ``w_up`` alone)
    expert_act: str = "swiglu"

    @classmethod
    def from_dict(cls, d: dict) -> "Lfm2Config":
        if d.get("conv_bias"):
            raise ValueError("conv_bias: true is not supported")
        layers = tuple(d["layer_types"][:int(d["num_hidden_layers"])])
        if len(layers) != int(d["num_hidden_layers"]) or set(layers) - {
                "conv", "full_attention"}:
            raise ValueError(f"layer_types {layers} do not give "
                             f"{d['num_hidden_layers']} known layers")
        return cls(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            rope_theta=float(d["rope_parameters"]["rope_theta"]),
            conv_L_cache=int(d["conv_L_cache"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            use_expert_bias=bool(d["use_expert_bias"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            num_dense_layers=int(d["num_dense_layers"]),
            norm_eps=float(d["norm_eps"]),
            vocab_size=int(d["vocab_size"]),
            layer_types=layers,
            head_dim=int(d.get("head_dim") or int(d["hidden_size"])
                         // int(d["num_attention_heads"])))

    def layers_of(self, kind: str) -> list:
        return [i for i, k in enumerate(self.layer_types) if k == kind]

    @property
    def expert_layers(self) -> list:
        return list(range(self.num_dense_layers, len(self.layer_types)))


# -- weights ------------------------------------------------------------------

def pack_layer(raw: dict) -> Params:
    """One layer from its tensors under the published names (``op.wq``,
    ``ffn.w1`` ... as nested dicts, bfloat16) to the layout the programs
    read: ``w1 | w3`` and ``wq | wk | wv`` side by side, so that each is one
    product; norms, the depthwise kernel, the router and its bias float32."""
    op, ffn = raw["op"], raw["ffn"]
    if "in_proj" in op:
        op_p = {"in_proj": op["in_proj"], "conv_w": op["conv_w"].astype(F32),
                "out_proj": op["out_proj"]}
    else:
        op_p = {"wqkv": jnp.concatenate([op["wq"], op["wk"], op["wv"]], -1),
                "wo": op["wo"], "q_norm": op["q_norm"].astype(F32),
                "k_norm": op["k_norm"].astype(F32)}
    ffn_p = {"w13": jnp.concatenate([ffn["w1"], ffn["w3"]], -1),
             "w2": ffn["w2"]}
    if "router" in ffn:
        ffn_p.update(router=ffn["router"].astype(F32),
                     expert_bias=ffn["expert_bias"].astype(F32))
    return {"op_norm": raw["op_norm"].astype(F32),
            "ffn_norm": raw["ffn_norm"].astype(F32),
            "op": op_p, "ffn": ffn_p}


# -- the convolution mixer ----------------------------------------------------

def conv_op_seq(u, p, n):
    """A row's prompt ``[T, H]`` whole; also the operator's state after
    ``n`` tokens: the last ``conv_L_cache`` columns of ``B * x``."""
    with jax.named_scope("conv_op"):
        k = p["conv_w"].shape[0]
        t = u.shape[0]
        b, c, x = jnp.split(mm(u, p["in_proj"]), 3, axis=-1)
        bx = jnp.pad(b * x, ((k, 0), (0, 0)))
        conv = sum(bx[j + 1:j + 1 + t] * p["conv_w"][j] for j in range(k))
        state = lax.dynamic_slice_in_dim(bx, n, k, axis=0)
        return mm(c * conv, p["out_proj"]), state


def conv_op_step(u, p, state):
    """One token of every slot ``[S, H]`` against the slots' states
    ``[S, conv_L_cache, H]``."""
    with jax.named_scope("conv_op"):
        b, c, x = jnp.split(mm(u, p["in_proj"]), 3, axis=-1)
        state = jnp.concatenate([state[:, 1:], (b * x)[:, None]], axis=1)
        conv = jnp.einsum("skh,kh->sh", state, p["conv_w"])
        return mm(c * conv, p["out_proj"]), state


def _ffn_half(h, p, i: int, cfg: Lfm2Config, held, valid, routes: list,
              loads: list):
    """``h + ffn(rms(h))`` of layer ``i``; an expert layer appends the
    experts chosen and its load."""
    u = rms_norm(h, p["ffn_norm"], cfg.norm_eps)
    if i < cfg.num_dense_layers:
        return h + dense_ffn(u, p["ffn"])
    out, chosen, load = moe_ffn(u, p["ffn"], cfg, held, valid)
    routes.append(chosen)
    loads.append(load)
    return h + out


# -- the generation state and the programs ------------------------------------

def new_cache(cfg: Lfm2Config, slots: int, positions: int) -> dict:
    """The state of ``slots`` rows of at most ``positions`` tokens: keys
    and values of the attention layers, the convolution layers' columns,
    and per slot the next token, its position, the units sampled so far
    and the experts every token chose.  What a step writes per slot and
    position (keys, values, the experts chosen) lies as
    :func:`~sonata_tpu.ops.slot_attention.stored_shape` says: a position
    is one row of whole lanes."""
    kv_shape = stored_shape(slots, positions, cfg.num_key_value_heads,
                            cfg.head_dim)
    n_attn, n_conv = (len(cfg.layers_of("full_attention")),
                      len(cfg.layers_of("conv")))
    return {
        "k": [jnp.zeros(kv_shape, BF16) for _ in range(n_attn)],
        "v": [jnp.zeros(kv_shape, BF16) for _ in range(n_attn)],
        "conv": [jnp.zeros((slots, cfg.conv_L_cache, cfg.hidden_size), F32)
                 for _ in range(n_conv)],
        "token": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "count": jnp.zeros((slots,), jnp.int32),
        "units": jnp.zeros((slots, positions), jnp.int32),
        "routes": jnp.zeros(stored_shape(
            slots, positions, len(cfg.expert_layers),
            cfg.num_experts_per_tok), jnp.int8),
    }


def prefill(params: Params, cache: dict, ids, n, slot, temperature, key, *,
            cfg: Lfm2Config, units: UnitIds, held=None):
    """One row joins: its prompt ``ids`` ``[T]`` (``n`` real) runs whole,
    its state goes into ``slot`` and its first unit is sampled from the
    logits at the prompt's last position.  Returns the cache, those logits
    ``[V]`` and the expert layers' load ``[expert layers, 3]``."""
    t = ids.shape[0]
    valid = jnp.arange(t) < n
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]),
                 conv=list(cache["conv"]))
    h = params["embed"][ids].astype(F32)
    i_attn = i_conv = 0
    routes, loads = [], []
    for i, kind in enumerate(cfg.layer_types):
        p = params["layers"][i]
        u = rms_norm(h, p["op_norm"], cfg.norm_eps)
        if kind == "conv":
            op, state = conv_op_seq(u, p["op"], n)
            cache["conv"][i_conv] = cache["conv"][i_conv].at[slot].set(state)
            i_conv += 1
        else:
            op, k, v = attn_op_seq(u, p["op"], cfg)
            cache["k"][i_attn] = write_slot(cache["k"][i_attn], k, slot)
            cache["v"][i_attn] = write_slot(cache["v"][i_attn], v, slot)
            i_attn += 1
        h = _ffn_half(h + op, p, i, cfg, held, valid, routes, loads)
    last = lax.dynamic_slice_in_dim(h, n - 1, 1, axis=0)
    logits = _head(last, params, cfg)
    cache = join(cache, slot, n, logits, routes, temperature, key, units)
    return cache, logits[0], jnp.stack(loads)


def step(params: Params, cache: dict, live, temperature, step_no, *,
         cfg: Lfm2Config, units: UnitIds, seed: int = 0, held=None):
    """Every slot advances by one token: the slot's last unit goes in at
    its position through the slot's state, and the next unit is sampled.
    ``live`` ``[S]`` says which slots hold a row: the others are computed
    (the shape is static) but cost no expert product, count for nothing
    and do not advance.  Returns the cache, the logits ``[S, V]`` and the
    expert layers' load ``[expert layers, 3]``."""
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]),
                 conv=list(cache["conv"]))
    pos = cache["pos"]
    h = params["embed"][cache["token"]].astype(F32)
    i_attn = i_conv = 0
    routes, loads = [], []
    for i, kind in enumerate(cfg.layer_types):
        p = params["layers"][i]
        u = rms_norm(h, p["op_norm"], cfg.norm_eps)
        if kind == "conv":
            op, cache["conv"][i_conv] = conv_op_step(
                u, p["op"], cache["conv"][i_conv])
            i_conv += 1
        else:
            op, cache["k"][i_attn], cache["v"][i_attn] = attn_op_step(
                u, p["op"], cfg, cache["k"][i_attn], cache["v"][i_attn], pos)
            i_attn += 1
        h = _ffn_half(h + op, p, i, cfg, held, live, routes, loads)
    logits = _head(h, params, cfg)
    cache = advance(cache, live, logits, routes, temperature, step_no, units,
                    seed)
    return cache, logits, jnp.stack(loads)


def step_admit(params: Params, cache: dict, live, temperature, step_no, ids,
               n, slot, row_temperature, row_key, *, cfg: Lfm2Config,
               units: UnitIds, seed: int = 0, held=None):
    """A step that carries an arrival: what :func:`step` over ``live`` and
    then :func:`prefill` of ``ids`` ``[T]`` (``n`` real) into ``slot`` give,
    in one launch.  Per layer the mixer runs once per kind of row (the
    slots' tokens through the slots' state, the prompt whole), and what is
    row-wise runs once over both, ``[S + T, H]``: the norms, the
    feed-forward, the expert layer (one sort, each touched expert read
    once), the head over the ``S`` rows and the prompt's last position.

    ``slot`` is not live here (its row steps from the next launch on): its
    stale place is computed like any empty slot's, and the prompt's write
    comes after the step's on every buffer, so it is the last word on the
    slot.  Returns the cache, the logits ``[S + 1, V]`` (the slots' rows as
    :func:`step` gives them, then the prompt's last position as
    :func:`prefill` does) and the load of both kinds of row together."""
    s, t = live.shape[0], ids.shape[0]
    valid = jnp.concatenate([live, jnp.arange(t) < n])
    cache = dict(cache, k=list(cache["k"]), v=list(cache["v"]),
                 conv=list(cache["conv"]))
    pos = cache["pos"]
    h = params["embed"][jnp.concatenate([cache["token"], ids])].astype(F32)
    i_attn = i_conv = 0
    routes, loads = [], []
    for i, kind in enumerate(cfg.layer_types):
        p = params["layers"][i]
        u = rms_norm(h, p["op_norm"], cfg.norm_eps)
        if kind == "conv":
            op, state = conv_op_step(u[:s], p["op"], cache["conv"][i_conv])
            joined, columns = conv_op_seq(u[s:], p["op"], n)
            cache["conv"][i_conv] = state.at[slot].set(columns)
            i_conv += 1
        else:
            op, k_buf, v_buf = attn_op_step(
                u[:s], p["op"], cfg, cache["k"][i_attn], cache["v"][i_attn],
                pos)
            joined, k, v = attn_op_seq(u[s:], p["op"], cfg)
            cache["k"][i_attn] = write_slot(k_buf, k, slot)
            cache["v"][i_attn] = write_slot(v_buf, v, slot)
            i_attn += 1
        h = _ffn_half(h + jnp.concatenate([op, joined]), p, i, cfg, held,
                      valid, routes, loads)
    cache, logits = advance_and_join(
        params, cache, h, routes, live, temperature, step_no, n, slot,
        row_temperature, row_key, cfg, units, seed)
    return cache, logits, jnp.stack(loads)


class Lfm2Backbone(TokenRows):
    """``lfm2_moe``: the prefill samples a row's first unit, and every step
    gives every live row one more."""

    pack_layer = staticmethod(pack_layer)
    build_step, build_prefill, build_step_admit = token_step_programs(
        sys.modules[__name__], "lfm2")

    def __init__(self, backbone: dict, units: dict, seed: int):
        self.cfg = Lfm2Config.from_dict(backbone)
        self.units = UnitIds(int(units["first_id"]), int(units["stop_id"]))
        self.layers = len(self.cfg.layer_types)
        self.attention_layers = len(self.cfg.layers_of("full_attention"))
        self.seed = seed

    def new_cache(self, slots: int, positions: int) -> dict:
        return new_cache(self.cfg, slots, positions)
