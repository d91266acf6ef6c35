"""A Nemotron-H backbone (``model_type: nemotron_h``) for step-wise
generation: Mamba-2 state beside keys and values, and an expert layer with
a shared expert, of whose routed experts this chip may hold a share.

Every layer is **one** mixer behind one norm, ``h += mixer(rms(h))``, the
mixer named by a character of ``hybrid_override_pattern``:

- ``M``, Mamba-2.  ``[z | xBC | dt] = u W_in``; ``xBC`` goes through a causal
  depthwise convolution (``conv_kernel`` taps, with bias) and a SiLU, and
  splits into ``x`` ``[heads, P]`` and ``B``, ``C`` ``[n_groups, N]`` (head
  ``j`` reads group ``j // (heads / n_groups)``); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head the state ``S`` ``[P, N]`` moves
  as ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` and gives ``y_t = S_t C_t
  + D x_t``; then ``y = w * rms_norm(y * silu(z))`` over each group's
  ``d_inner / n_groups`` channels (the gate before the norm) and ``out = y
  W_out``.  State: ``S`` in float32 and the last ``conv_kernel - 1`` columns
  of ``xBC``.  Two forms that agree: :func:`mamba_seq` runs a prompt in
  chunks of ``chunk_size`` (products inside a chunk, the recurrence between
  chunks), :func:`mamba_step` the recurrence itself, one token a slot.
- ``*``, grouped-query attention, causal, **with no position encoding**
  (the family's published modeling code applies none), no head norms.
- ``E``, the expert layer: :func:`~.lfm2.route`'s sigmoid router (selection
  by ``sigmoid + e_score_correction_bias``, weights the unbiased scores of
  the chosen, normalised, times ``routed_scaling_factor``), experts
  ``relu(u W_up)^2 W_down`` (no gate), plus a shared expert of the same form
  that every token takes and that is counted once.  :func:`~.lfm2.moe_ffn`
  runs it; ``held = (first, count)`` says which of the router's
  ``num_experts`` experts this chip holds (a configuration's
  ``expert_parallel`` block), and what the others would add is left out.

A final RMS norm, then a head of its own.

**State a slot holds** (:func:`new_cache`): keys and values of the
attention layers, and per Mamba layer the recurrent state ``[heads, P, N]``
(float32: it does not grow with the row) and the convolution's columns.
Keys and values are masked by position; a recurrent state is not, so a row
that joins must not see what the slot's last row left: :func:`prefill`
starts every Mamba layer from zero and *writes* the slot's state whole.

**Precision**, as :mod:`.lfm2` states it, and: the recurrent state, the
convolution and its columns, ``dt``, the decays and the gated norm are
float32; the products inside a chunk run float32 at ``highest`` (they are a
thousandth of a prefill).
"""

from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.slot_attention import stored_shape, write_slot
from .unit_backbone import Description, TokenRows, token_step_programs
from .unit_layers import BF16, F32, UnitIds, _head, advance, \
    advance_and_join, attn_op_seq, attn_op_step, join, mm, moe_ffn, \
    pad_experts, rms_norm

Params = dict
#: a layer's mixer by its character of the pattern: Mamba-2, attention,
#: experts
KINDS = "M*E"


@dataclasses.dataclass(frozen=True)
class NemotronConfig:
    """The backbone's published ``config.json`` keys that shape the graph
    (and, constant for the family, what :mod:`.lfm2`'s pieces ask)."""

    hidden_size: int
    pattern: str
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    moe_intermediate_size: int
    num_experts: int            #: the router's width
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    norm_eps: float
    vocab_size: int
    #: the routed experts this chip holds: ``(first, count)``
    held: tuple
    tie_word_embeddings: bool = False
    router_scoring: str = "sigmoid"
    use_expert_bias: bool = True
    expert_act: str = "relu2"

    @classmethod
    def from_dict(cls, d: dict) -> "NemotronConfig":
        pattern = str(d["hybrid_override_pattern"])
        if len(pattern) != int(d["num_hidden_layers"]) or set(pattern) - set(
                KINDS):
            raise ValueError(f"hybrid_override_pattern {pattern!r} does not "
                             f"give {d['num_hidden_layers']} known layers")
        if d.get("mlp_hidden_act") != "relu2" or d.get("use_bias") or d.get(
                "mamba_proj_bias") or d.get("attention_bias") or not d.get(
                "use_conv_bias") or int(d.get("n_group", 1)) != 1 or int(
                d.get("n_shared_experts", 1)) != 1:
            raise ValueError("only relu2 experts, one shared expert, one "
                             "router group, a biased convolution and no "
                             "other bias are supported")
        held_here = int(d["n_routed_experts"])
        share = d.get("expert_parallel") or {
            "routed_experts": held_here, "held": [0, held_here]}
        held = tuple(int(v) for v in share["held"])
        if held[1] != held_here or held[0] < 0 or sum(held) > int(
                share["routed_experts"]):
            raise ValueError(f"held = {held} is not n_routed_experts = "
                             f"{held_here} of {share['routed_experts']}")
        heads, groups = int(d["mamba_num_heads"]), int(d["n_groups"])
        if heads % groups or heads * int(d["mamba_head_dim"]) % groups:
            raise ValueError(f"{groups} groups do not divide {heads} heads")
        return cls(
            hidden_size=int(d["hidden_size"]), pattern=pattern,
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]), mamba_num_heads=heads,
            mamba_head_dim=int(d["mamba_head_dim"]), n_groups=groups,
            ssm_state_size=int(d["ssm_state_size"]),
            conv_kernel=int(d["conv_kernel"]),
            chunk_size=int(d["chunk_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(share["routed_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            norm_eps=float(d["layer_norm_epsilon"]),
            vocab_size=int(d["vocab_size"]), held=held,
            tie_word_embeddings=bool(d["tie_word_embeddings"]))

    def layers_of(self, kind: str) -> list:
        return [i for i, c in enumerate(self.pattern) if c == kind]

    @property
    def expert_layers(self) -> list:
        return self.layers_of("E")

    @property
    def d_inner(self) -> int:
        """Heads x head size (not ``expand`` x hidden)."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``x | B | C``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def ssm_state_bytes(self) -> int:
        """Bytes of one slot's recurrent state and convolution columns,
        over all Mamba layers (float32)."""
        per = (self.d_inner * self.ssm_state_size
               + (self.conv_kernel - 1) * self.conv_dim)
        return 4 * per * len(self.layers_of("M"))


def pack_layer(raw: dict) -> Params:
    """One layer from its tensors under the reference's names (bfloat16) to
    the layout the programs read: ``wq | wk | wv`` side by side; norms, the
    depthwise kernel and its bias, ``A_log``, ``D``, ``dt_bias``, the router
    and its bias float32.  An expert layer's ``w_up`` is ``w13`` here: one
    matrix of width ``I`` (``relu2`` has no gate), and ``I`` lies in whole
    lanes (:func:`~.lfm2.pad_experts`: 1856 columns in 1920)."""
    mixer = raw["mixer"]
    norm = raw["norm"].astype(F32)
    if "in_proj" in mixer:
        out = {k: mixer[k].astype(F32) for k in (
            "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm")}
        return {"norm": norm, "mixer": dict(
            out, in_proj=mixer["in_proj"], out_proj=mixer["out_proj"])}
    if "wq" in mixer:
        return {"norm": norm, "mixer": {
            "wqkv": jnp.concatenate([mixer["wq"], mixer["wk"], mixer["wv"]],
                                    -1), "wo": mixer["wo"]}}
    w13, w2 = pad_experts(mixer["w_up"], mixer["w_down"])
    return {"norm": norm, "mixer": {
        "router": mixer["router"].astype(F32),
        "expert_bias": mixer["e_score_correction_bias"].astype(F32),
        "w13": w13, "w2": w2,
        "shared": {"w_up": mixer["shared_up"],
                   "w_down": mixer["shared_down"]}}}


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def _qkv(u, p, cfg, positions):
    """Queries, keys and values as they are: no rotary, no head norm."""
    n = u.shape[0]
    heads, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
    qkv = mm(u, p["wqkv"])
    q = qkv[:, :heads * d].reshape(n, heads, d)
    k = qkv[:, heads * d:(heads + kv) * d].reshape(n, kv, d)
    v = qkv[:, (heads + kv) * d:].reshape(n, kv, d)
    return q, k.astype(BF16), v.astype(BF16)


def _split_in(zxbcdt, cfg: NemotronConfig):
    d, c = cfg.d_inner, cfg.conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + c], zxbcdt[..., d + c:]


def _split_conv(xbc, cfg: NemotronConfig):
    """``x`` ``[..., heads, P]`` and ``B``, ``C`` ``[..., heads, N]``: each
    head with its group's ``B`` and ``C``."""
    d, g, n = cfg.d_inner, cfg.n_groups, cfg.ssm_state_size
    lead = xbc.shape[:-1]
    x = xbc[..., :d].reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim)
    per = cfg.mamba_num_heads // g
    b = jnp.repeat(xbc[..., d:d + g * n].reshape(*lead, g, n), per, axis=-2)
    c = jnp.repeat(xbc[..., d + g * n:].reshape(*lead, g, n), per, axis=-2)
    return x, b, c


def _gated_out(y, z, p, cfg: NemotronConfig):
    """``(w * rms_norm_per_group(y * silu(z))) W_out``."""
    lead = y.shape[:-1]
    g = (y * jax.nn.silu(z)).reshape(*lead, cfg.n_groups, -1)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.norm_eps)
    return mm(g.reshape(*lead, -1) * p["norm"], p["out_proj"])


def mamba_seq(u, p, cfg: NemotronConfig, n):
    """A row's prompt ``[T, H]`` whole (``n`` real), from a zero state, in
    chunks of ``chunk_size``; also the layer's state after ``n`` tokens:
    ``S`` ``[heads, P, N]`` and the last ``conv_kernel - 1`` columns of
    ``xBC``.  Padding does not move the state (its ``dt`` is 0)."""
    with jax.named_scope("ssm_op"):
        t, k, size = u.shape[0], cfg.conv_kernel, cfg.chunk_size
        z, xbc, dt = _split_in(mm(u, p["in_proj"]), cfg)
        padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        conv_state = lax.dynamic_slice_in_dim(padded, n, k - 1, axis=0)
        conv = sum(padded[j:j + t] * p["conv_w"][j] for j in range(k))
        x, b, c = _split_conv(jax.nn.silu(conv + p["conv_b"]), cfg)
        dt = jnp.where((jnp.arange(t) < n)[:, None],
                       jax.nn.softplus(dt + p["dt_bias"]), 0.0)
        a = dt * -jnp.exp(p["A_log"])                       # [T, heads]
        dtx = dt[..., None] * x
        pad = -t % size
        chunks = (t + pad) // size

        def chunked(v):
            v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
            return v.reshape(chunks, size, *v.shape[1:])

        a, dtx, b, c = chunked(a), chunked(dtx), chunked(b), chunked(c)
        cum = jnp.cumsum(a, axis=1)                         # [c, L, heads]
        seen = jnp.tril(jnp.ones((size, size), bool))
        decay = jnp.exp(jnp.where(
            seen[None, :, :, None], cum[:, :, None] - cum[:, None], -jnp.inf))
        with jax.default_matmul_precision("highest"):
            inside = jnp.einsum("clhn,cshn->clsh", c, b) * decay
            y = jnp.einsum("clsh,cshp->clhp", inside, dtx)
            # what each chunk adds to the state by its end, and the state
            # before each chunk: the recurrence, a chunk a step
            to_end = jnp.exp(cum[:, -1:] - cum)
            adds = jnp.einsum("clh,clhp,clhn->chpn", to_end, dtx, b)
            state = jnp.zeros(adds.shape[1:], F32)
            before = []
            for j in range(chunks):
                before.append(state)
                state = jnp.exp(cum[j, -1])[:, None, None] * state + adds[j]
            y = y + jnp.einsum("clhn,chpn,clh->clhp", c, jnp.stack(before),
                               jnp.exp(cum))
        y = y.reshape(chunks * size, -1)[:t] + (p["D"][:, None] * x).reshape(
            t, -1)
        return _gated_out(y, z, p, cfg), state, conv_state


def mamba_step(u, p, cfg: NemotronConfig, state, conv_state):
    """One token of every slot ``[S, H]`` through the slots' states ``[S,
    heads, P, N]`` and convolution columns ``[S, conv_kernel - 1, C]``."""
    with jax.named_scope("ssm_op"):
        z, xbc, dt = _split_in(mm(u, p["in_proj"]), cfg)
        window = jnp.concatenate([conv_state, xbc[:, None]], axis=1)
        conv = jnp.einsum("skc,kc->sc", window, p["conv_w"]) + p["conv_b"]
        x, b, c = _split_conv(jax.nn.silu(conv), cfg)
        dt = jax.nn.softplus(dt + p["dt_bias"])             # [S, heads]
        decay = jnp.exp(dt * -jnp.exp(p["A_log"]))
        state = decay[..., None, None] * state \
            + (dt[..., None] * x)[..., None] * b[:, :, None, :]
        y = jnp.sum(state * c[:, :, None, :], -1) + p["D"][:, None] * x
        return (_gated_out(y.reshape(u.shape[0], -1), z, p, cfg), state,
                window[:, 1:])


def _experts(u, p, cfg: NemotronConfig, valid, routes: list, loads: list):
    out, chosen, load = moe_ffn(u, p, cfg, cfg.held, valid)
    routes.append(chosen)
    loads.append(load)
    return out


# ---------------------------------------------------------------------------
# the generation state and the programs
# ---------------------------------------------------------------------------

def new_cache(cfg: NemotronConfig, slots: int, positions: int) -> dict:
    """The state of ``slots`` rows of at most ``positions`` tokens: keys
    and values of the attention layers, the Mamba layers' recurrent states
    and convolution columns, and per slot the next token, its position, the
    units sampled so far and the experts every token chose.  Keys, values
    and the experts chosen lie as
    :func:`~sonata_tpu.ops.slot_attention.stored_shape` says."""
    kv_shape = stored_shape(slots, positions, cfg.num_key_value_heads,
                            cfg.head_dim)
    n_attn, n_ssm = len(cfg.layers_of("*")), len(cfg.layers_of("M"))
    return {
        "k": [jnp.zeros(kv_shape, BF16) for _ in range(n_attn)],
        "v": [jnp.zeros(kv_shape, BF16) for _ in range(n_attn)],
        "ssm": [jnp.zeros((slots, cfg.mamba_num_heads, cfg.mamba_head_dim,
                           cfg.ssm_state_size), F32) for _ in range(n_ssm)],
        "conv": [jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_dim), F32)
                 for _ in range(n_ssm)],
        "token": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "count": jnp.zeros((slots,), jnp.int32),
        "units": jnp.zeros((slots, positions), jnp.int32),
        "routes": jnp.zeros(stored_shape(
            slots, positions, len(cfg.expert_layers),
            cfg.num_experts_per_tok), jnp.int8),
    }


def _open(cache: dict) -> dict:
    return dict(cache, **{k: list(cache[k])
                          for k in ("k", "v", "ssm", "conv")})


def prefill(params: Params, cache: dict, ids, n, slot, temperature, key, *,
            cfg: NemotronConfig, units: UnitIds):
    """One row joins: its prompt ``ids`` ``[T]`` (``n`` real) runs whole
    from a zero state, what it leaves goes into ``slot`` (whatever the
    slot's last row left there is overwritten, recurrent state and all),
    and its first unit is sampled from the logits at the prompt's last
    position.  Returns the cache, those logits ``[V]`` and the expert
    layers' load ``[expert layers, 5]``."""
    t = ids.shape[0]
    valid = jnp.arange(t) < n
    cache = _open(cache)
    h = params["embed"][ids].astype(F32)
    i_attn = i_ssm = 0
    routes, loads = [], []
    for i, kind in enumerate(cfg.pattern):
        p = params["layers"][i]
        u = rms_norm(h, p["norm"], cfg.norm_eps)
        if kind == "M":
            out, state, conv = mamba_seq(u, p["mixer"], cfg, n)
            cache["ssm"][i_ssm] = cache["ssm"][i_ssm].at[slot].set(state)
            cache["conv"][i_ssm] = cache["conv"][i_ssm].at[slot].set(conv)
            i_ssm += 1
        elif kind == "*":
            out, k, v = attn_op_seq(u, p["mixer"], cfg, qkv=_qkv)
            cache["k"][i_attn] = write_slot(cache["k"][i_attn], k, slot)
            cache["v"][i_attn] = write_slot(cache["v"][i_attn], v, slot)
            i_attn += 1
        else:
            out = _experts(u, p["mixer"], cfg, valid, routes, loads)
        h = h + out
    logits = _head(lax.dynamic_slice_in_dim(h, n - 1, 1, axis=0), params,
                   cfg)
    cache = join(cache, slot, n, logits, routes, temperature, key, units)
    return cache, logits[0], jnp.stack(loads)


def step(params: Params, cache: dict, live, temperature, step_no, *,
         cfg: NemotronConfig, units: UnitIds, seed: int = 0):
    """Every slot advances by one token: the slot's last unit goes in at
    its position through the slot's state, and the next unit is sampled.
    ``live`` ``[S]`` says which slots hold a row: the others are computed
    (the shape is static; their states move, and the next row's prefill
    overwrites them) but cost no expert product, count for nothing and do
    not advance.  Returns the cache, the logits ``[S, V]`` and the expert
    layers' load ``[expert layers, 5]``."""
    cache = _open(cache)
    pos = cache["pos"]
    h = params["embed"][cache["token"]].astype(F32)
    i_attn = i_ssm = 0
    routes, loads = [], []
    for i, kind in enumerate(cfg.pattern):
        p = params["layers"][i]
        u = rms_norm(h, p["norm"], cfg.norm_eps)
        if kind == "M":
            out, cache["ssm"][i_ssm], cache["conv"][i_ssm] = mamba_step(
                u, p["mixer"], cfg, cache["ssm"][i_ssm],
                cache["conv"][i_ssm])
            i_ssm += 1
        elif kind == "*":
            out, cache["k"][i_attn], cache["v"][i_attn] = attn_op_step(
                u, p["mixer"], cfg, cache["k"][i_attn], cache["v"][i_attn],
                pos, qkv=_qkv)
            i_attn += 1
        else:
            out = _experts(u, p["mixer"], cfg, live, routes, loads)
        h = h + out
    logits = _head(h, params, cfg)
    cache = advance(cache, live, logits, routes, temperature, step_no, units,
                    seed)
    return cache, logits, jnp.stack(loads)


def step_admit(params: Params, cache: dict, live, temperature, step_no, ids,
               n, slot, row_temperature, row_key, *, cfg: NemotronConfig,
               units: UnitIds, seed: int = 0):
    """A step that carries an arrival (:func:`~.lfm2.step_admit` says what
    that is): :func:`step` over ``live`` and :func:`prefill` of ``ids``
    ``[T]`` (``n`` real) into ``slot`` in one launch.  A Mamba or attention
    layer runs once per kind of row, an expert layer once over both.
    ``slot``'s stale state moves with the step like any empty slot's, and
    the prompt's state, written after, replaces it whole.  Returns the
    cache, the logits ``[S + 1, V]`` (the slots' rows, then the prompt's
    last position) and the load of both kinds of row together."""
    s, t = live.shape[0], ids.shape[0]
    valid = jnp.concatenate([live, jnp.arange(t) < n])
    cache = _open(cache)
    pos = cache["pos"]
    h = params["embed"][jnp.concatenate([cache["token"], ids])].astype(F32)
    i_attn = i_ssm = 0
    routes, loads = [], []
    for i, kind in enumerate(cfg.pattern):
        p = params["layers"][i]
        u = rms_norm(h, p["norm"], cfg.norm_eps)
        if kind == "M":
            out, states, columns = mamba_step(
                u[:s], p["mixer"], cfg, cache["ssm"][i_ssm],
                cache["conv"][i_ssm])
            joined, state, conv = mamba_seq(u[s:], p["mixer"], cfg, n)
            cache["ssm"][i_ssm] = states.at[slot].set(state)
            cache["conv"][i_ssm] = columns.at[slot].set(conv)
            i_ssm += 1
            out = jnp.concatenate([out, joined])
        elif kind == "*":
            out, k_buf, v_buf = attn_op_step(
                u[:s], p["mixer"], cfg, cache["k"][i_attn],
                cache["v"][i_attn], pos, qkv=_qkv)
            joined, k, v = attn_op_seq(u[s:], p["mixer"], cfg, qkv=_qkv)
            cache["k"][i_attn] = write_slot(k_buf, k, slot)
            cache["v"][i_attn] = write_slot(v_buf, v, slot)
            i_attn += 1
            out = jnp.concatenate([out, joined])
        else:
            out = _experts(u, p["mixer"], cfg, valid, routes, loads)
        h = h + out
    cache, logits = advance_and_join(
        params, cache, h, routes, live, temperature, step_no, n, slot,
        row_temperature, row_key, cfg, units, seed)
    return cache, logits, jnp.stack(loads)


class NemotronBackbone(TokenRows):
    """``nemotron_h``: a row gains a token a step; the programs and what a
    slot holds are its own."""

    pack_layer = staticmethod(pack_layer)

    def __init__(self, backbone: dict, units: dict, seed: int):
        self.cfg = NemotronConfig.from_dict(backbone)
        self.units = UnitIds(int(units["first_id"]), int(units["stop_id"]))
        self.layers = len(self.cfg.pattern)
        self.attention_layers = len(self.cfg.layers_of("*"))
        self.seed = seed
        self.held = self.cfg.held

    def new_cache(self, slots: int, positions: int) -> dict:
        return new_cache(self.cfg, slots, positions)

    def record(self, cache, slot: int) -> tuple:
        return (*super().record(cache, slot), cache["ssm"][-1][slot])

    def dump(self, ids: list, budget: int, kept: list, record) -> dict:
        """A token row's dump and the recurrent state ``[heads, P, N]`` the
        row left in the last Mamba layer: what no logit shows apart (the
        products' bfloat16 inputs cover a state kept in less than
        float32)."""
        return dict(super().dump(ids, budget, kept, record[:2]),
                    state=record[2])

    def describe(self, slots: int, positions: int) -> Description:
        """Beside the keys and values: the Mamba layers, the recurrent
        state and convolution columns a slot holds for them (a live row's
        step reads and writes its slot's), and the chunks their scans run
        over a prompt padded to its text bucket."""
        base = super().describe(slots, positions)
        layers = len(self.cfg.layers_of("M"))
        state, chunk = self.cfg.ssm_state_bytes, self.cfg.chunk_size
        return dataclasses.replace(
            base, static=dict(base.static, ssm_layers=layers),
            closed=lambda g: dict(base.closed(g), ssm_state_bytes=(
                2 * state * g["live_slot_steps"])),
            resident={"sonata_ssm_state_resident_bytes": slots * state},
            prefill=lambda text_bucket: {
                "ssm_chunks": -(-text_bucket // chunk) * layers})

    build_step, build_prefill, build_step_admit = token_step_programs(
        sys.modules[__name__], "nemotron")
