"""The GigaChat-3.5 unit voices' writer: a voice of the ``unit_lm`` family
with a ``gigachat3_5`` backbone, from a seed.  Its configuration lives under
``perfbench/configs/gigachat/`` (a file a configuration: the published
``config.json`` keys at the top level, ``expert_parallel`` and
``vocab_parallel`` for the chip's share, ``reduced`` / ``published`` /
``deployment`` / ``assumed`` / ``not_served`` / ``precision`` / ``memory``
in prose, the server's command and the ``voice`` block).  ``lfm2gen.py``'s
way: ``write_voice`` leaves ``voice.json``, ``generator.npz`` and
``recipe.json`` (the seed); the backbone's weights are a *recipe*,
``draw(config, name)``, that the configuration's server command
(``perfbench/harness/gigachat_serve.py``) expands on the device layer by
layer and the comparison expands again for the reference, so both read the
same bfloat16 numbers and neither made them.

The recipe is ``lfm2gen``'s (the ``n``-th tensor of ``tensor_specs`` is
``centre + bound * uniform(-1, 1)`` from ``fold_in(PRNGKey(seed), n)``,
rounded to its storage type; bounds variance preserving; the embedding at
unit variance and the head, a matrix of its own, scaled so that the logits
spread by about ``LOGIT_STD``).  What this family adds:

- **a norm's weight is what stands under its sigmoid** (the gain is ``2
  sigmoid(w)``): the four norms of a layer are drawn about ``NORM_CENTRES``
  (gains about 1.0, 0.6, 1.2 and 0.8), the two inside latent attention
  about gains 1.0 and 0.9, the linear layers' ``o_norm`` (zero-centred:
  ``1 + w``) about 0.1, so that a norm dropped, swapped for its neighbour
  or read as its own gain (``w`` about 0 in ``2 sigmoid(w)``'s place)
  moves the logits;
- a linear layer's ``A_log`` about -1 and ``dt_bias`` about -3 under a
  ``wa`` at half the variance-preserving width: ``alpha``'s rate ``exp(
  A_log) softplus(a + dt_bias)`` lies about 0.003-0.1, so a state forgets
  over some ten to three hundred steps (one that forgot at once, or never,
  would let a stale or an undecayed state pass unseen); ``wb`` wide enough
  that ``beta`` spreads over (0.1, 0.9), ``wz`` and ``wg`` that the gates
  spread over most of (0, 1);
- every SwiGLU's ``w1`` and ``w3`` are ``SWIGLU_IN_GAIN`` times wider than
  variance preserving, so that about one pre-activation in a hundred
  passes ``swiglu_limit`` and the clamp binds (the norm behind every
  feed-forward takes the scale out again);
- the router is drawn at its published width
  (``expert_parallel.routed_experts``) whatever share of the experts the
  chip holds, ``ROUTER_GAIN`` times wider than variance preserving;
  ``e_score_correction_bias`` is small and non-zero;
- embedding, head and unit table have the rows of the vocabulary held here.

Nothing here imports jax while the module is loaded (``run.py`` loads it).
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from perfbench.harness import lfm2gen, voicegen
from perfbench.harness.lfm2gen import EXPERT_OUT_GAIN, FFN_OUT_GAIN, \
    LOGIT_STD, NORM_SPREAD, _linear, generator_flat, nest, \
    reference_params  # noqa: F401

#: the configuration's top-level keys that are the backbone's published
#: ``config.json``, and the chip's share of experts and vocabulary: they go
#: into the voice JSON's ``backbone`` block as they stand
BACKBONE_KEYS = (
    "model_type", "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "nextn_is_sparse", "num_attention_heads", "n_shared_experts",
    "n_routed_experts", "routed_scaling_factor", "kv_lora_rank",
    "q_lora_rank", "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
    "qk_head_dim", "n_group", "topk_group", "num_experts_per_tok",
    "first_k_dense_replace", "norm_topk_prob", "rope_interleave",
    "num_key_value_heads", "hidden_act", "rms_norm_eps", "rope_theta",
    "rope_scaling", "attention_bias", "norm_type", "layernorm_type",
    "layernorm_gating_weight", "gated_attention",
    "use_shared_expert_sigmoid", "use_mla_scaling_factor",
    "linear_attention_type", "full_attention_layers", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_num_key_heads", "linear_num_value_heads", "linear_gating_type",
    "linear_sigmoid_gate_scale", "linear_attn_o_norm_eps", "swiglu_limit",
    "tie_word_embeddings", "num_nextn_predict_layers", "tf_legacy_loss",
    "expert_parallel", "vocab_parallel")

#: a layer's four norms, by name, and the centre each weight is drawn about:
#: ``logit(gain / 2)`` of gains 1.0, 0.6, 1.2 and 0.8
NORM_CENTRES = {"input_norm": 0.0, "post_attn_norm": -0.847,
                "pre_mlp_norm": 0.405, "post_mlp_norm": -0.405}
Q_NORM_CENTRE, KV_NORM_CENTRE = 0.0, -0.2
O_NORM_CENTRE = 0.1
A_LOG = (-1.0, 0.5)
DT_BIAS = (-3.0, 0.7)
DECAY_IN_GAIN = 0.5
BETA_IN_GAIN = 1.3
GATE_IN_GAIN = 1.5
SWIGLU_IN_GAIN = 4.0
ROUTER_GAIN = 2.0
EXPERT_BIAS_BOUND = 0.05


def backbone(config: dict) -> dict:
    return {k: config[k] for k in BACKBONE_KEYS}


def voice_json(config: dict) -> dict:
    return dict(config["voice"], backbone=backbone(config))


def mixer_specs(bb: dict, i: int, p: str) -> list:
    """Layer ``i``'s mixer: gated latent attention in a layer of
    ``full_attention_layers``, else the gated DeltaNet."""
    h = int(bb["hidden_size"])
    if i in bb["full_attention_layers"]:
        heads = int(bb["num_attention_heads"])
        q_rank, c = int(bb["q_lora_rank"]), int(bb["kv_lora_rank"])
        nope, rope, v = (int(bb["qk_nope_head_dim"]),
                         int(bb["qk_rope_head_dim"]), int(bb["v_head_dim"]))
        return [
            (p + "wq_a", (h, q_rank), "bfloat16", 0.0, _linear(h)),
            (p + "q_norm", (q_rank,), "bfloat16", Q_NORM_CENTRE, NORM_SPREAD),
            (p + "wq_b", (q_rank, heads * (nope + rope)), "bfloat16", 0.0,
             _linear(q_rank)),
            (p + "wkv_a", (h, c + rope), "bfloat16", 0.0, _linear(h)),
            (p + "kv_norm", (c,), "bfloat16", KV_NORM_CENTRE, NORM_SPREAD),
            (p + "wkv_b", (c, heads * (nope + v)), "bfloat16", 0.0,
             _linear(c)),
            (p + "wg", (h, heads * v), "bfloat16", 0.0,
             _linear(h, GATE_IN_GAIN)),
            (p + "wo", (heads * v, h), "bfloat16", 0.0, _linear(heads * v))]
    kh, vh = int(bb["linear_num_key_heads"]), int(
        bb["linear_num_value_heads"])
    dk, dv = int(bb["linear_key_head_dim"]), int(bb["linear_value_head_dim"])
    taps, conv = int(bb["linear_conv_kernel_dim"]), 2 * kh * dk + vh * dv
    return [
        (p + "wqkv", (h, conv), "bfloat16", 0.0, _linear(h)),
        (p + "wz", (h, vh * dv), "bfloat16", 0.0, _linear(h, GATE_IN_GAIN)),
        (p + "wb", (h, vh), "bfloat16", 0.0, _linear(h, BETA_IN_GAIN)),
        (p + "wa", (h, vh), "bfloat16", 0.0, _linear(h, DECAY_IN_GAIN)),
        (p + "conv_w", (taps, conv), "bfloat16", 0.0, _linear(taps)),
        (p + "A_log", (vh,), "float32", *A_LOG),
        (p + "dt_bias", (vh,), "float32", *DT_BIAS),
        (p + "o_norm", (dv,), "bfloat16", O_NORM_CENTRE, NORM_SPREAD),
        (p + "wout", (vh * dv, h), "bfloat16", 0.0, _linear(vh * dv))]


def layer_specs(bb: dict, i: int) -> list:
    """``(name, shape, storage type, centre, bound)`` of layer ``i``'s
    tensors, under the reference's names."""
    h = int(bb["hidden_size"])
    p = f"layers.{i}."
    out = [(p + name, (h,), "bfloat16", centre, NORM_SPREAD)
           for name, centre in NORM_CENTRES.items()]
    out += mixer_specs(bb, i, p + "mixer.")
    wide = _linear(h, SWIGLU_IN_GAIN)
    if i < int(bb["first_k_dense_replace"]):
        m = int(bb["intermediate_size"])
        return out + [
            (p + "ffn.w1", (h, m), "bfloat16", 0.0, wide),
            (p + "ffn.w3", (h, m), "bfloat16", 0.0, wide),
            (p + "ffn.w2", (m, h), "bfloat16", 0.0,
             _linear(m, FFN_OUT_GAIN))]
    m = int(bb["moe_intermediate_size"])
    s = m * int(bb["n_shared_experts"])
    held = int(bb["n_routed_experts"])
    routed = int((bb.get("expert_parallel") or {}).get("routed_experts",
                                                       held))
    return out + [
        (p + "ffn.router", (h, routed), "bfloat16", 0.0,
         _linear(h, ROUTER_GAIN)),
        (p + "ffn.e_score_correction_bias", (routed,), "float32", 0.0,
         EXPERT_BIAS_BOUND),
        (p + "ffn.w1", (held, h, m), "bfloat16", 0.0, wide),
        (p + "ffn.w3", (held, h, m), "bfloat16", 0.0, wide),
        (p + "ffn.w2", (held, m, h), "bfloat16", 0.0,
         _linear(m, EXPERT_OUT_GAIN)),
        (p + "ffn.shared_w1", (h, s), "bfloat16", 0.0, wide),
        (p + "ffn.shared_w3", (h, s), "bfloat16", 0.0, wide),
        (p + "ffn.shared_w2", (s, h), "bfloat16", 0.0,
         _linear(s, FFN_OUT_GAIN))]


def tensor_specs(config: dict) -> list:
    """Every tensor of the voice but the generator's, in the recipe's
    order (a tensor's place in this list is part of its key)."""
    bb = backbone(config)
    h, v = int(bb["hidden_size"]), int(bb["vocab_size"])
    latent = int(voicegen.model_dims(config["voice"])["inter_channels"])
    out = [("embed", (v, h), "bfloat16", 0.0, math.sqrt(3.0)),
           ("head", (v, h), "bfloat16", 0.0,
            math.sqrt(3.0) * LOGIT_STD / math.sqrt(h)),
           ("norm_f", (h,), "bfloat16", 0.0, NORM_SPREAD),
           ("unit_table", (v, latent), "float32", 0.0, math.sqrt(3.0))]
    for i in range(int(bb["num_hidden_layers"])):
        out += layer_specs(bb, i)
    return out


def parameters(config: dict) -> dict:
    """Parameters of the backbone by part, counted from the recipe's
    shapes: what the configuration's arithmetic is checked against."""
    out: dict = {}
    for name, shape, *_ in tensor_specs(config):
        if name == "unit_table":
            continue
        part = name.split(".")
        key = part[0] if len(part) == 1 else f"layer{part[1]}.{part[2]}"
        out[key] = out.get(key, 0) + math.prod(shape)
    return out


def draw(config: dict, name: str):
    """One tensor of the recipe, on the default device, in its storage
    type."""
    import jax

    specs = tensor_specs(config)
    n = next(k for k, s in enumerate(specs) if s[0] == name)
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(config["weights"]["seed"])), n)
    return lfm2gen._maker(*specs[n][1:])(key)


def draw_layer(config: dict, i: int) -> dict:
    """Layer ``i`` as the reference names it: the four norms, ``mixer`` and
    ``ffn``, storage types as drawn."""
    prefix = f"layers.{i}."
    return nest({s[0][len(prefix):]: draw(config, s[0])
                 for s in layer_specs(backbone(config), i)})


def write_voice(out_dir, config: dict) -> Path:
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    path = out / "voice.json"
    path.write_text(json.dumps(voice_json(config)))
    with open(out / "generator.npz", "wb") as f:
        np.savez(f, **generator_flat(config))
    (out / "recipe.json").write_text(json.dumps(
        {"seed": int(config["weights"]["seed"]),
         "tensors": len(tensor_specs(config))}))
    return path


def write_tensors(out_dir, config: dict) -> Path:
    """The same voice as a directory of real tensors, the format the
    product's own loader reads.  For sizes that fit a disk: the tests'
    tiny voice."""
    import jax.numpy as jnp
    from jax import lax

    path = write_voice(out_dir, config)
    tensors = Path(out_dir) / "tensors"
    tensors.mkdir(exist_ok=True)
    for name, _, dtype, _, _ in tensor_specs(config):
        value = draw(config, name)
        if dtype == "bfloat16":
            np.save(tensors / f"{name}.bf16.npy", np.asarray(
                lax.bitcast_convert_type(value, jnp.uint16)))
        else:
            np.save(tensors / f"{name}.f32.npy", np.asarray(value))
    return path


def describe(config: dict) -> dict:
    dims = voicegen.model_dims(config["voice"])
    return {"samples_per_frame": math.prod(dims["upsample_rates"]),
            "num_speakers": 1,
            "dims": dict(dims, backbone=backbone(config),
                         units=config["voice"]["units"]),
            "frame_budget_estimator": False}
