"""A GigaChat-3.5 backbone (``model_type: gigachat3_5``) for step-wise
generation: gated delta-rule linear attention in most layers, gated latent
attention (MLA) in the ``full_attention_layers``, four zero-centred gated
norms a layer, a clamped SwiGLU, and an expert layer of whose routed
experts, like the vocabulary's rows, this chip may hold a thin share.

Every norm of the backbone is ``N_w(x) = x / sqrt(mean(x^2) + eps) * g *
sigmoid(w)`` (``norm_type: ZeroCenteredGatedNorm``, ``g =
layernorm_gating_weight`` = 2: a gain that is 1 at ``w = 0``);
:func:`pack_layer` turns ``w`` into that gain once, so the programs run
:func:`~.unit_layers.rms_norm`.  Every layer, on the residual ``h``
(``layernorm_type: pre_post``)::

    h = h + N_2(mixer(N_1(h)))
    h = h + N_4(ffn(N_3(h)))

**Linear layers** (gated DeltaNet).  With ``x = N_1(h)``: ``[q | k | v | z |
b | a] = x W_in``; a causal depthwise convolution of
``linear_conv_kernel_dim`` taps over ``[q | k | v]`` and a SiLU; ``q`` and
``k`` L2-normalised a head, ``q`` times ``d_k^-1/2``, each key head serving
``value heads / key heads`` value heads; ``beta = sigmoid(b)``, ``alpha =
exp(-exp(A_log) softplus(a + dt_bias))``; per value head the state ``S``
``[d_k, d_v]`` moves by the **delta rule**, which reads the state with the
new key before it writes it::

    S_t = alpha_t S_{t-1} + k_t (x) beta_t (v_t - alpha_t S_{t-1}^T k_t)
    o_t = S_t^T q_t

then ``y = rms(o_t; a head) (1 + w_o) * s * sigmoid(z)`` (``s =
linear_sigmoid_gate_scale``) and ``out = y W_out``.  Two forms that agree:
:func:`delta_seq` runs a prompt in chunks of :data:`CHUNK` (inside a chunk
the WY form: the chunk's updates ``delta`` solve one unit triangular system,
``(I + A) delta = beta v - (beta e^c k) S_0``; the recurrence between
chunks); :func:`delta_step` is the recurrence itself, one token a slot,
written so that the old state is read once for both of its reductions
(``S^T k`` and ``S^T q``: ``o_t = alpha S^T q + (k . q) delta``) and once
more for the update that writes it.

**Full layers**: :mod:`.pangu_moe`'s latent attention as it stands
(:func:`~.pangu_moe.mla_in`, the expanded form for a prompt, the absorbed
one for a step, one cached row ``[c_kv | k_r]`` a position) with YaRN's
paces on the rotary dimensions (:mod:`.laguna`'s), the softmax's scale
times ``m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``
(``use_mla_scaling_factor``), and a **gate**: the heads' result times
``sigmoid(x W_g)``, element by element, before ``W_o``.

``ffn`` is a dense SwiGLU in the first ``first_k_dense_replace`` layers and
after them the expert layer: :func:`~.unit_layers.route`'s sigmoid router
with the correction bias, SwiGLU experts and a shared expert every token
takes, :func:`~.unit_layers.moe_ffn` over the experts held here
(``expert_parallel``).  Every SwiGLU is clamped (``swiglu_limit``).  A
final norm, then a head of its own over the vocabulary's rows held here
(``vocab_parallel``).

**State a slot holds** (:func:`new_cache`): per linear layer the matrix a
value head ``[heads, d_k, d_v]`` (float32: it does not grow with the row)
and the convolution's columns; per full layer one latent row a position.
Latent rows are masked by position; a recurrent state is not, so
:func:`prefill` starts every linear layer from zero and *writes* the slot's
state whole.

**Not served:** the multi-token prediction modules
(``num_nextn_predict_layers``): a step that proposes and verifies more than
one unit a row needs the loop to learn a row's progress from the device.

**Precision**, as :mod:`.unit_layers` states it, and: the delta-rule state,
``alpha``, ``beta``, the L2 norms, the convolution and its columns, both
reductions over the state and the gated norm are float32; the products
inside a chunk run float32 at ``highest``; latent rows are bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import sys

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.slot_attention import latent_implementation, latent_places, \
    latent_reach, stored_shape, write_rows, write_slot
from .laguna import Rotary, rotate, yarn_inv_freq
from .pangu_moe import mla_in, mla_seq, mla_step
from .unit_backbone import Description, TokenRows, token_step_programs
from .unit_layers import BF16, F32, UnitIds, _expert_act, _head, advance, \
    advance_and_join, join, mm, moe_ffn, rms_norm

Params = dict
#: positions a chunk of :func:`delta_seq` holds
CHUNK = 64
#: what stands under the root of an L2 norm's sum of squares
L2_EPS = 1e-6
NORMS = ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")


def _share(d: dict, block: str, total: str, held_here: int) -> tuple:
    """``(all, first, count)`` of what ``block`` (``expert_parallel``,
    ``vocab_parallel``) says this chip holds of ``total``."""
    share = d.get(block) or {total: held_here, "held": [0, held_here]}
    first, count = (int(v) for v in share["held"])
    if count != held_here or first < 0 or first + count > int(share[total]):
        raise ValueError(f"{block}.held = {share['held']} is not "
                         f"{held_here} of {share[total]}")
    return int(share[total]), first, count


@dataclasses.dataclass(frozen=True)
class GigaChatConfig:
    """The backbone's published ``config.json`` keys that shape the graph
    (and, constant for the family, what :mod:`.unit_layers`' and
    :mod:`.pangu_moe`'s pieces ask)."""

    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    full_attention_layers: tuple
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rotary: Rotary              #: YaRN's paces over the rope dimensions
    softmax_scale: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_attn_o_norm_eps: float
    linear_sigmoid_gate_scale: float
    layernorm_gating_weight: float
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int            #: the router's width
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    swiglu_limit: float
    norm_eps: float
    vocab_size: int             #: the vocabulary's rows held here
    #: the routed experts this chip holds: ``(first, count)``
    held: tuple
    tie_word_embeddings: bool = False
    router_scoring: str = "sigmoid"
    use_expert_bias: bool = True
    expert_act: str = "swiglu_clamped"

    @classmethod
    def from_dict(cls, d: dict) -> "GigaChatConfig":
        if d.get("norm_type") != "ZeroCenteredGatedNorm" or d.get(
                "layernorm_type") != "pre_post" or not d.get(
                "gated_attention") or d.get("attention_bias") or d.get(
                "hidden_act") != "silu" or d.get(
                "use_shared_expert_sigmoid") or d.get(
                "linear_gating_type") != \
                "gated_rmsnorm_sigmoid_zero_centered" or int(
                d.get("n_shared_experts", 1)) != 1 or int(
                d.get("n_group", 1)) != 1 or int(
                d["num_key_value_heads"]) != int(d["num_attention_heads"]):
            raise ValueError(
                "only zero-centred gated norms before and after each "
                "sublayer, a gated attention without bias, SiLU, one shared "
                "expert without a gate, one router group and the "
                "zero-centred sigmoid gate of the linear layers are "
                "supported")
        layers, dense = (int(d["num_hidden_layers"]),
                         int(d["first_k_dense_replace"]))
        full = tuple(int(i) for i in d["full_attention_layers"])
        if not 0 <= dense <= layers or any(not 0 <= i < layers for i in full):
            raise ValueError(
                f"first_k_dense_replace {dense} and full_attention_layers "
                f"{list(full)} of {layers} layers")
        key_heads, value_heads = (int(d["linear_num_key_heads"]),
                                  int(d["linear_num_value_heads"]))
        if value_heads % key_heads:
            raise ValueError(f"{key_heads} key heads do not divide "
                             f"{value_heads} value heads")
        experts, first, count = _share(d, "expert_parallel", "routed_experts",
                                       int(d["n_routed_experts"]))
        vocab = int(d["vocab_size"])
        if _share(d, "vocab_parallel", "vocab_size", vocab)[1]:
            # a slice that starts elsewhere would need the other chips'
            # embedding rows for the ids it is fed
            raise ValueError("vocab_parallel.held does not start at id 0")
        rope, scaling = int(d["qk_rope_head_dim"]), d["rope_scaling"]
        if scaling.get("type") != "yarn" or float(scaling["mscale"]) != float(
                scaling["mscale_all_dim"]):
            raise ValueError("only YaRN with mscale = mscale_all_dim (cos "
                             "and sin unscaled) is supported")
        factor = float(scaling["factor"])
        paces = yarn_inv_freq(
            rope, float(d["rope_theta"]), factor,
            int(scaling["original_max_position_embeddings"]),
            float(scaling["beta_fast"]), float(scaling["beta_slow"]))
        scale = float(int(d["qk_nope_head_dim"]) + rope) ** -0.5
        if d.get("use_mla_scaling_factor"):
            scale *= (0.1 * float(scaling["mscale_all_dim"])
                      * math.log(factor) + 1.0) ** 2
        return cls(
            hidden_size=int(d["hidden_size"]), num_hidden_layers=layers,
            first_k_dense_replace=dense, full_attention_layers=full,
            num_attention_heads=int(d["num_attention_heads"]),
            q_lora_rank=int(d["q_lora_rank"]),
            kv_lora_rank=int(d["kv_lora_rank"]),
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=rope, v_head_dim=int(d["v_head_dim"]),
            rotary=Rotary(rope, tuple(paces)), softmax_scale=scale,
            linear_num_key_heads=key_heads,
            linear_num_value_heads=value_heads,
            linear_key_head_dim=int(d["linear_key_head_dim"]),
            linear_value_head_dim=int(d["linear_value_head_dim"]),
            linear_conv_kernel_dim=int(d["linear_conv_kernel_dim"]),
            linear_attn_o_norm_eps=float(d["linear_attn_o_norm_eps"]),
            linear_sigmoid_gate_scale=float(d["linear_sigmoid_gate_scale"]),
            layernorm_gating_weight=float(d["layernorm_gating_weight"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=experts,
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            norm_topk_prob=bool(d["norm_topk_prob"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            swiglu_limit=float(d["swiglu_limit"]),
            norm_eps=float(d["rms_norm_eps"]), vocab_size=vocab,
            held=(first, count),
            tie_word_embeddings=bool(d["tie_word_embeddings"]))

    @property
    def linear_layers(self) -> list:
        return [i for i in range(self.num_hidden_layers)
                if i not in self.full_attention_layers]

    @property
    def expert_layers(self) -> list:
        return list(range(self.first_k_dense_replace, self.num_hidden_layers))

    @property
    def latent_width(self) -> int:
        """Values of a cached row: ``c_kv | k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``q | k | v``."""
        return 2 * self.key_width + self.value_width

    @property
    def delta_state_bytes(self) -> int:
        """Bytes of one slot's delta-rule states and convolution columns,
        over all linear layers (float32)."""
        per = (self.value_width * self.linear_key_head_dim
               + (self.linear_conv_kernel_dim - 1) * self.conv_dim)
        return 4 * per * len(self.linear_layers)

    def latent_cache_bytes(self, positions: int) -> int:
        """Bytes of ``positions`` cached rows over the full layers, as
        stored."""
        return 2 * len(self.full_attention_layers) * positions \
            * stored_shape(1, 1, 1, self.latent_width)[-1]


def gain(w, cfg: GigaChatConfig):
    """A zero-centred gated norm's weight as the gain it stands for."""
    return cfg.layernorm_gating_weight * jax.nn.sigmoid(w.astype(F32))


def pack_layer(raw: dict, cfg: GigaChatConfig) -> Params:
    """One layer from its tensors under the reference's names (bfloat16) to
    the layout the programs read.  Every zero-centred norm's weight becomes
    its gain (:func:`gain`; float32).  A linear mixer: ``W_qkv | W_z | W_b |
    W_a`` side by side as ``in_proj`` (one product of ``x``), the depthwise
    kernel, ``A_log``, ``dt_bias`` and ``1 + w_o`` float32.  A full one, as
    :func:`~.pangu_moe.pack_layer`: ``W_qa | W_kva`` side by side, ``W_kvb``
    as its key half and its value half, each ``[heads, c, d]``; the gate's
    ``W_g`` beside them.  ``w1 | w3`` side by side as ``w13`` (the shared
    expert's as ``w_up``); the router and its bias float32."""
    mixer, ffn = raw["mixer"], raw["ffn"]
    if "wqkv" in mixer:
        op = {"in_proj": jnp.concatenate(
            [mixer["wqkv"], mixer["wz"], mixer["wb"], mixer["wa"]], -1),
            "conv_w": mixer["conv_w"].astype(F32),
            "A_log": mixer["A_log"].astype(F32),
            "dt_bias": mixer["dt_bias"].astype(F32),
            "o_gain": 1.0 + mixer["o_norm"].astype(F32),
            "out_proj": mixer["wout"]}
    else:
        per_head = mixer["wkv_b"].reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim).transpose(1, 0, 2)
        op = {"wqkv_a": jnp.concatenate([mixer["wq_a"], mixer["wkv_a"]], -1),
              "wq_b": mixer["wq_b"],
              "wk_b": per_head[..., :cfg.qk_nope_head_dim],
              "wv_b": per_head[..., cfg.qk_nope_head_dim:],
              "wg": mixer["wg"], "wo": mixer["wo"],
              "q_norm": gain(mixer["q_norm"], cfg),
              "kv_norm": gain(mixer["kv_norm"], cfg)}
    ffn_p = {"w13": jnp.concatenate([ffn["w1"], ffn["w3"]], -1),
             "w2": ffn["w2"]}
    if "router" in ffn:
        ffn_p.update(
            router=ffn["router"].astype(F32),
            expert_bias=ffn["e_score_correction_bias"].astype(F32),
            shared={"w_up": jnp.concatenate([ffn["shared_w1"],
                                             ffn["shared_w3"]], -1),
                    "w_down": ffn["shared_w2"]})
    return dict({k: gain(raw[k], cfg) for k in NORMS}, mixer=op, ffn=ffn_p)


# ---------------------------------------------------------------------------
# gated delta-rule linear attention
# ---------------------------------------------------------------------------

def delta_in(x, p, cfg: GigaChatConfig) -> tuple:
    """Row-wise over ``x`` ``[N, H]``: ``[q | k | v]`` before its
    convolution ``[N, conv_dim]``, the gate's ``z`` ``[N, value_width]`` and
    ``b``, ``a`` ``[N, value heads]``."""
    c, d, h = cfg.conv_dim, cfg.value_width, cfg.linear_num_value_heads
    out = mm(x, p["in_proj"])
    return (out[:, :c], out[:, c:c + d], out[:, c + d:c + d + h],
            out[:, c + d + h:])


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _heads(qkv, cfg: GigaChatConfig) -> tuple:
    """``[q | k | v]`` behind its convolution as ``q``, ``k`` ``[..., value
    heads, d_k]`` (L2-normalised a head, ``q`` scaled, each key head once
    for every value head it serves) and ``v`` ``[..., value heads, d_v]``."""
    lead, w = qkv.shape[:-1], cfg.key_width
    kh, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    per = cfg.linear_num_value_heads // kh
    q = _l2(qkv[..., :w].reshape(*lead, kh, dk)) * dk ** -0.5
    k = _l2(qkv[..., w:2 * w].reshape(*lead, kh, dk))
    v = qkv[..., 2 * w:].reshape(*lead, cfg.linear_num_value_heads,
                                 cfg.linear_value_head_dim)
    return jnp.repeat(q, per, axis=-2), jnp.repeat(k, per, axis=-2), v


def _gates(b, a, p) -> tuple:
    """``beta`` and ``log alpha`` ``[..., value heads]``."""
    return (jax.nn.sigmoid(b),
            -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))


def delta_seq(qkv, b, a, p, cfg: GigaChatConfig, n) -> tuple:
    """A row's prompt whole (``[T, ...]`` of :func:`delta_in`, ``n`` real),
    from a zero state, in chunks of :data:`CHUNK`: ``o`` ``[T, value heads,
    d_v]`` and the layer's state after ``n`` tokens, ``S`` ``[value heads,
    d_k, d_v]`` and the last ``linear_conv_kernel_dim - 1`` columns of ``[q
    | k | v]``.  Padding does not move the state (its ``beta`` is 0, its
    ``alpha`` 1)."""
    with jax.named_scope("delta_op"):
        t, taps = qkv.shape[0], cfg.linear_conv_kernel_dim
        padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
        conv_state = lax.dynamic_slice_in_dim(padded, n, taps - 1, axis=0)
        conv = sum(padded[j:j + t] * p["conv_w"][j] for j in range(taps))
        q, k, v = _heads(jax.nn.silu(conv), cfg)
        real = (jnp.arange(t) < n)[:, None]
        beta, log_alpha = _gates(b, a, p)
        beta, log_alpha = (jnp.where(real, beta, 0.0),
                           jnp.where(real, log_alpha, 0.0))
        pad = -t % CHUNK
        chunks = (t + pad) // CHUNK

        def chunked(x):
            """``[T, heads, ...]`` as ``[chunks, heads, CHUNK, ...]``."""
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            x = x.reshape(chunks, CHUNK, *x.shape[1:])
            return jnp.swapaxes(x, 1, 2)

        q, k, v, beta, log_alpha = (chunked(x) for x in (
            q, k, v, beta, log_alpha))
        cum = jnp.cumsum(log_alpha, axis=-1)                # [c, heads, L]
        seen = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
        decay = jnp.exp(jnp.where(
            seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        k_beta = k * beta[..., None]
        with jax.default_matmul_precision("highest"):
            # the chunk's updates solve (I + A) delta = beta v - (beta e^c
            # k) S_0, A strictly lower: both right-hand sides at once
            a_mat = jnp.einsum("chid,chjd->chij", k_beta, k) * decay
            solved = jax.scipy.linalg.solve_triangular(
                a_mat, jnp.concatenate(
                    [v * beta[..., None], k_beta * jnp.exp(cum)[..., None]],
                    -1), lower=True, unit_diagonal=True)
            own = solved[..., :v.shape[-1]]
            of_state = solved[..., v.shape[-1]:]
            inside = jnp.einsum("chid,chjd->chij", q, k) * decay
            q_decayed = q * jnp.exp(cum)[..., None]
            to_end = k * jnp.exp(cum[..., -1:] - cum)[..., None]

            def chunk(state, at):
                """The recurrence, a chunk a step."""
                own_j, of_state_j, q_j, inside_j, to_end_j, last = at
                delta = own_j - of_state_j @ state
                out = q_j @ state + inside_j @ delta
                return (jnp.exp(last)[:, None, None] * state + jnp.einsum(
                    "hld,hlv->hdv", to_end_j, delta)), out

            state, out = lax.scan(
                chunk, jnp.zeros(k.shape[1:2] + (k.shape[-1], v.shape[-1]),
                                 F32),
                (own, of_state, q_decayed, inside, to_end, cum[..., -1]))
        o = jnp.swapaxes(out, 1, 2)
        return o.reshape(chunks * CHUNK, *o.shape[2:])[:t], state, conv_state


def delta_step(qkv, b, a, p, cfg: GigaChatConfig, state, conv_state) -> tuple:
    """One token of every slot (``[S, ...]`` of :func:`delta_in`) through
    the slots' states ``[S, value heads, d_k, d_v]`` and convolution columns
    ``[S, linear_conv_kernel_dim - 1, conv_dim]``: ``o`` ``[S, value heads,
    d_v]``, the states and the columns.  The old state is read once for
    ``S^T k`` and ``S^T q`` together and once for the update."""
    with jax.named_scope("delta_op"):
        window = jnp.concatenate([conv_state, qkv[:, None]], axis=1)
        conv = jnp.einsum("skc,kc->sc", window, p["conv_w"])
        q, k, v = _heads(jax.nn.silu(conv), cfg)
        beta, log_alpha = _gates(b, a, p)
        alpha = jnp.exp(log_alpha)[..., None]
        read = jnp.sum(state[:, :, None] * jnp.stack([k, q], 2)[..., None],
                       axis=-2)                     # [S, heads, 2, d_v]
        delta = beta[..., None] * (v - alpha * read[:, :, 0])
        o = alpha * read[:, :, 1] + jnp.sum(k * q, -1, keepdims=True) * delta
        state = alpha[..., None] * state + k[..., None] * delta[..., None, :]
        return o, state, window[:, 1:]


def delta_out(o, z, p, cfg: GigaChatConfig):
    """``(rms(o; a head) (1 + w_o) * s * sigmoid(z)) W_out`` over ``o``
    ``[N, value heads, d_v]`` and ``z`` ``[N, value_width]``."""
    with jax.named_scope("delta_op"):
        normed = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                               + cfg.linear_attn_o_norm_eps) * p["o_gain"]
        gate = cfg.linear_sigmoid_gate_scale * jax.nn.sigmoid(z)
        return mm(normed.reshape(z.shape) * gate, p["out_proj"])


# ---------------------------------------------------------------------------
# a layer around its mixer
# ---------------------------------------------------------------------------

def _latent_in(x, p, cfg: GigaChatConfig, positions):
    """:func:`~.pangu_moe.mla_in` under YaRN's paces."""
    return mla_in(x, p, cfg, positions,
                  rotary=lambda v, at: rotate(v, at, cfg.rotary))


def _latent_out(x, attended, p):
    """The gate on the heads' result, then ``W_o``."""
    return mm(attended * jax.nn.sigmoid(mm(x, p["wg"])), p["wo"])


def _ffn(u, p, i: int, cfg: GigaChatConfig, valid, routes: list, loads: list):
    """Layer ``i``'s feed-forward of ``u``; an expert layer appends the
    experts chosen and its load."""
    if i < cfg.first_k_dense_replace:
        with jax.named_scope("dense_ffn"):
            return mm(_expert_act(mm(u, p["ffn"]["w13"]), cfg),
                      p["ffn"]["w2"])
    out, chosen, load = moe_ffn(u, p["ffn"], cfg, cfg.held, valid)
    routes.append(chosen)
    loads.append(load)
    return out


def _close(h, mixed, p, i: int, cfg: GigaChatConfig, valid, routes, loads):
    """The layer from behind its mixer: the norm after it, and the
    feed-forward between its two norms."""
    eps = cfg.norm_eps
    h = h + rms_norm(mixed, p["post_attn_norm"], eps)
    m = _ffn(rms_norm(h, p["pre_mlp_norm"], eps), p, i, cfg, valid, routes,
             loads)
    return h + rms_norm(m, p["post_mlp_norm"], eps)


def _places(cfg: GigaChatConfig) -> list:
    """Layer by layer, which of its kind's buffers a layer's state lies in:
    ``("latent", j)`` or ``("delta", j)``."""
    counts = {"latent": 0, "delta": 0}
    out = []
    for i in range(cfg.num_hidden_layers):
        kind = "latent" if i in cfg.full_attention_layers else "delta"
        out.append((kind, counts[kind]))
        counts[kind] += 1
    return out


def _open(params: Params, cache: dict, cfg: GigaChatConfig) -> tuple:
    """The parameters with the final norm's gain in its weight's place, and
    the cache with lists of its own."""
    return (dict(params, norm_f=gain(params["norm_f"], cfg)),
            dict(cache, **{k: list(cache[k])
                           for k in ("latent", "delta", "conv")}))


# ---------------------------------------------------------------------------
# the generation state and the programs
# ---------------------------------------------------------------------------

def new_cache(cfg: GigaChatConfig, slots: int, positions: int) -> dict:
    """The state of ``slots`` rows of at most ``positions`` tokens: per full
    layer the latent rows, one a position; per linear layer the delta-rule
    state, a matrix a value head, and the convolution's columns; and per
    slot the next token, its position, the units sampled so far and the
    experts every token chose (unsigned bytes: the router has 256 outputs).
    Rows lie as :func:`~sonata_tpu.ops.slot_attention.stored_shape` says."""
    row = stored_shape(slots, positions, 1, cfg.latent_width)
    linear = len(cfg.linear_layers)
    return {
        "latent": [jnp.zeros(row, BF16) for _ in cfg.full_attention_layers],
        "delta": [jnp.zeros((slots, cfg.linear_num_value_heads,
                             cfg.linear_key_head_dim,
                             cfg.linear_value_head_dim), F32)
                  for _ in range(linear)],
        "conv": [jnp.zeros((slots, cfg.linear_conv_kernel_dim - 1,
                            cfg.conv_dim), F32) for _ in range(linear)],
        "token": jnp.zeros((slots,), jnp.int32),
        "pos": jnp.zeros((slots,), jnp.int32),
        "count": jnp.zeros((slots,), jnp.int32),
        "units": jnp.zeros((slots, positions), jnp.int32),
        "routes": jnp.zeros(stored_shape(
            slots, positions, len(cfg.expert_layers),
            cfg.num_experts_per_tok), jnp.uint8),
    }


def prefill(params: Params, cache: dict, ids, n, slot, temperature, key, *,
            cfg: GigaChatConfig, units: UnitIds):
    """One row joins: its prompt ``ids`` ``[T]`` (``n`` real) runs whole
    (the chunked form from a zero state in the linear layers, the expanded
    form in the full ones), what it leaves goes into ``slot`` (whatever the
    slot's last row left there is overwritten, recurrent state and all) and
    its first unit is sampled from the logits at the prompt's last
    position.  Returns the cache, those logits ``[V]`` and the expert
    layers' load."""
    t = ids.shape[0]
    valid = jnp.arange(t) < n
    params, cache = _open(params, cache, cfg)
    h = params["embed"][ids].astype(F32)
    pos = jnp.arange(t)
    routes, loads = [], []
    for i, (p, (kind, j)) in enumerate(zip(params["layers"], _places(cfg))):
        x, op = rms_norm(h, p["input_norm"], cfg.norm_eps), p["mixer"]
        if kind == "latent":
            with jax.named_scope("mla_op"):
                q_nope, q_rope, row = _latent_in(x, op, cfg, pos)
                attended = mla_seq(q_nope, q_rope, row, op, cfg)
                cache["latent"][j] = write_slot(cache["latent"][j], row, slot)
                mixed = _latent_out(x, attended, op)
        else:
            qkv, z, b, a = delta_in(x, op, cfg)
            o, state, conv = delta_seq(qkv, b, a, op, cfg, n)
            cache["delta"][j] = cache["delta"][j].at[slot].set(state)
            cache["conv"][j] = cache["conv"][j].at[slot].set(conv)
            mixed = delta_out(o, z, op, cfg)
        h = _close(h, mixed, p, i, cfg, valid, routes, loads)
    logits = _head(lax.dynamic_slice_in_dim(h, n - 1, 1, axis=0), params,
                   cfg)
    cache = join(cache, slot, n, logits, routes, temperature, key, units)
    return cache, logits[0], jnp.stack(loads)


def step(params: Params, cache: dict, live, temperature, step_no, *,
         cfg: GigaChatConfig, units: UnitIds, seed: int = 0):
    """Every slot advances by one token: the slot's last unit goes in at
    its position through the slot's states (the recurrence) and latent rows
    (the absorbed form), and the next unit is sampled.  ``live`` ``[S]``
    says which slots hold a row: the others are computed (the shape is
    static; their states move, and the next row's prefill overwrites them)
    but cost no expert product, count for nothing and do not advance.
    Returns the cache, the logits ``[S, V]`` and the expert layers' load."""
    params, cache = _open(params, cache, cfg)
    pos = cache["pos"]
    h = params["embed"][cache["token"]].astype(F32)
    routes, loads = [], []
    for i, (p, (kind, j)) in enumerate(zip(params["layers"], _places(cfg))):
        x, op = rms_norm(h, p["input_norm"], cfg.norm_eps), p["mixer"]
        if kind == "latent":
            with jax.named_scope("mla_op"):
                q_nope, q_rope, row = _latent_in(x, op, cfg, pos)
                cache["latent"][j] = write_rows(
                    cache["latent"][j], row[:, None], pos[:, None])
                attended = mla_step(q_nope, q_rope, cache["latent"][j],
                                    pos + 1, op, cfg)
                mixed = _latent_out(x, attended, op)
        else:
            qkv, z, b, a = delta_in(x, op, cfg)
            o, cache["delta"][j], cache["conv"][j] = delta_step(
                qkv, b, a, op, cfg, cache["delta"][j], cache["conv"][j])
            mixed = delta_out(o, z, op, cfg)
        h = _close(h, mixed, p, i, cfg, live, routes, loads)
    logits = _head(h, params, cfg)
    cache = advance(cache, live, logits, routes, temperature, step_no, units,
                    seed)
    return cache, logits, jnp.stack(loads)


def step_admit(params: Params, cache: dict, live, temperature, step_no, ids,
               n, slot, row_temperature, row_key, *, cfg: GigaChatConfig,
               units: UnitIds, seed: int = 0):
    """A step that carries an arrival (:func:`~.lfm2.step_admit` says what
    that is): :func:`step` over ``live`` and :func:`prefill` of ``ids``
    ``[T]`` (``n`` real) into ``slot`` in one launch.  Everything row-wise
    runs once over ``[S + T, H]``, the mixers' own projections, gates and
    norms among it; between them the slots' rows take the recurrence (the
    absorbed form) and the prompt the chunked form (the expanded one).
    ``slot``'s stale state moves with the step like any empty slot's, and
    the prompt's state and rows, written after, replace it whole.  Returns
    the cache, the logits ``[S + 1, V]`` (the slots' rows, then the
    prompt's last position) and the load of both kinds of row together."""
    s, t = live.shape[0], ids.shape[0]
    valid = jnp.concatenate([live, jnp.arange(t) < n])
    params, cache = _open(params, cache, cfg)
    pos = cache["pos"]
    at = jnp.concatenate([pos, jnp.arange(t)])
    h = params["embed"][jnp.concatenate([cache["token"], ids])].astype(F32)
    routes, loads = [], []
    for i, (p, (kind, j)) in enumerate(zip(params["layers"], _places(cfg))):
        x, op = rms_norm(h, p["input_norm"], cfg.norm_eps), p["mixer"]
        if kind == "latent":
            with jax.named_scope("mla_op"):
                q_nope, q_rope, row = _latent_in(x, op, cfg, at)
                buf = write_rows(cache["latent"][j], row[:s, None],
                                 pos[:, None])
                attended = jnp.concatenate([
                    mla_step(q_nope[:s], q_rope[:s], buf, pos + 1, op, cfg),
                    mla_seq(q_nope[s:], q_rope[s:], row[s:], op, cfg)])
                cache["latent"][j] = write_slot(buf, row[s:], slot)
                mixed = _latent_out(x, attended, op)
        else:
            qkv, z, b, a = delta_in(x, op, cfg)
            stepped, states, columns = delta_step(
                qkv[:s], b[:s], a[:s], op, cfg, cache["delta"][j],
                cache["conv"][j])
            joined, state, conv = delta_seq(qkv[s:], b[s:], a[s:], op, cfg, n)
            cache["delta"][j] = states.at[slot].set(state)
            cache["conv"][j] = columns.at[slot].set(conv)
            mixed = delta_out(jnp.concatenate([stepped, joined]), z, op, cfg)
        h = _close(h, mixed, p, i, cfg, valid, routes, loads)
    cache, logits = advance_and_join(
        params, cache, h, routes, live, temperature, step_no, n, slot,
        row_temperature, row_key, cfg, units, seed)
    return cache, logits, jnp.stack(loads)


class GigaChatBackbone(TokenRows):
    """``gigachat3_5``: a row gains a token a step; the programs and what a
    slot holds (a matrix a value head in the linear layers, one latent row
    a position in the full ones) are its own."""

    def __init__(self, backbone: dict, units: dict, seed: int):
        self.cfg = GigaChatConfig.from_dict(backbone)
        self.units = UnitIds(int(units["first_id"]), int(units["stop_id"]))
        self.layers = self.cfg.num_hidden_layers
        self.seed = seed
        self.held = self.cfg.held
        self.pack_layer = functools.partial(pack_layer, cfg=self.cfg)

    def new_cache(self, slots: int, positions: int) -> dict:
        return new_cache(self.cfg, slots, positions)

    def _latent_shape(self, positions: int) -> tuple:
        cfg = self.cfg
        return (positions, cfg.num_attention_heads, cfg.latent_width,
                cfg.kv_lora_rank, self.block_length)

    def attention(self, positions: int) -> str:
        return latent_implementation(*self._latent_shape(positions))

    def record(self, cache, slot: int) -> tuple:
        return (*super().record(cache, slot), cache["delta"][-1][slot])

    def dump(self, ids: list, budget: int, kept: list, record) -> dict:
        """A token row's dump and the delta-rule state ``[value heads, d_k,
        d_v]`` the row left in the last linear layer: what no logit shows
        apart."""
        return dict(super().dump(ids, budget, kept, record[:2]),
                    state=record[2])

    def describe(self, slots: int, positions: int) -> Description:
        """The linear layers, the delta-rule state and convolution columns a
        slot holds for them (a live row's step reads and writes its
        slot's), and the chunks their scans run over a prompt padded to its
        text bucket; the full layers' latent rows as
        :meth:`~.pangu_moe.PanguBackbone.describe` says them (the places a
        layer's reader moves, the bytes of the rows read, as stored)."""
        base = super().describe(slots, positions)
        cfg = self.cfg
        linear, full = len(cfg.linear_layers), len(cfg.full_attention_layers)
        chunk = latent_reach(*self._latent_shape(positions))
        state, row_bytes = cfg.delta_state_bytes, cfg.latent_cache_bytes
        return dataclasses.replace(
            base, static=dict(base.static, delta_layers=linear,
                              latent_layers=full, mla_form="absorbed"),
            rows=[(latent_places(n, chunk), kv)
                  for n, (_, kv) in enumerate(base.rows)],
            closed=lambda g: dict(
                base.closed(g),
                delta_state_bytes=2 * state * g["live_slot_steps"],
                latent_cache_bytes=row_bytes(g["kv_positions"])),
            resident={
                "sonata_delta_state_resident_bytes": slots * state,
                "sonata_mla_cache_resident_bytes":
                    row_bytes(slots * positions)},
            prefill=lambda text_bucket: {
                "delta_chunks": -(-text_bucket // CHUNK) * linear,
                "mla_form": "expanded"})

    build_step, build_prefill, build_step_admit = token_step_programs(
        sys.modules[__name__], "gigachat")
