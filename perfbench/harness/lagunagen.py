"""The Laguna unit voices' writer: a voice of the ``unit_lm`` family with a
``laguna`` backbone, from a seed.  Its configuration lives under
``perfbench/configs/laguna/`` (a file a configuration: the published
``config.json`` keys at the top level, ``expert_parallel`` for the chip's
share, ``reduced`` / ``published`` / ``deployment`` / ``assumed`` /
``precision`` / ``memory`` in prose, the server's command and the ``voice``
block).  ``lfm2gen.py``'s way: ``write_voice`` leaves ``voice.json``,
``generator.npz`` and ``recipe.json`` (the seed); the backbone's weights are
a *recipe*, ``draw(config, name)``, that the configuration's server command
(``perfbench/harness/laguna_serve.py``) expands on the device layer by layer
and the comparison expands again for the reference, so both read the same
bfloat16 numbers and neither made them.

The recipe is ``lfm2gen``'s (the ``n``-th tensor of ``tensor_specs`` is
``centre + bound * uniform(-1, 1)`` from ``fold_in(PRNGKey(seed), n)``,
rounded to its storage type; bounds variance preserving; the embedding at
unit variance and the head, a matrix of its own, scaled so that the logits
spread by about ``LOGIT_STD``, as ``pangugen``).  What this family adds:

- a layer's query heads are its own (``num_attention_heads_per_layer``),
  so ``wq``, ``wg`` and ``wo`` differ in shape between the two kinds;
- ``wq`` and ``wk`` are ``QK_GAIN`` times wider than variance preserving
  each, so that the scores spread by about 2 (3 on a full layer's rotated
  half, YaRN's factor squared) and the softmax leans on a few positions:
  which positions a layer sees (the window, a stale place of the ring) and
  how they are rotated then moves the logits by far more than rounding;
- the gate ``wg`` is variance preserving, so that its sigmoid spreads over
  about 0.2-0.8: a gate dropped doubles some heads and leaves others;
- the router is drawn at its published width
  (``expert_parallel.routed_experts``) whatever share of the experts the
  chip holds (``num_experts``, the held experts' tensors), ``ROUTER_GAIN``
  times wider than variance preserving, without bias (as ``pangugen``).

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
#: ``config.json``, and the chip's share of the experts: they go into the
#: voice JSON's ``backbone`` block as they stand
BACKBONE_KEYS = (
    "model_type", "vocab_size", "hidden_size", "intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "max_position_embeddings", "attention_bias", "rms_norm_eps",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "tie_word_embeddings", "gating",
    "sliding_window", "rope_parameters", "layer_types",
    "moe_apply_router_weight_on_input", "partial_rotary_factor",
    "mlp_layer_types", "moe_routed_scaling_factor",
    "num_attention_heads_per_layer", "expert_parallel")

QK_GAIN = math.sqrt(2.0)
ROUTER_GAIN = 2.0


def backbone(config: dict) -> dict:
    return {k: config[k] for k in BACKBONE_KEYS}


def voice_json(config: dict) -> dict:
    return dict(config["voice"], backbone=backbone(config))


def layer_specs(bb: dict, i: int) -> list:
    """``(name, shape, storage type, centre, bound)`` of layer ``i``'s
    tensors, under the reference's names."""
    h, kv, d = (int(bb["hidden_size"]), int(bb["num_key_value_heads"]),
                int(bb["head_dim"]))
    heads = int(bb["num_attention_heads_per_layer"][i])
    p = f"layers.{i}."
    out = [
        (p + "attn_norm", (h,), "bfloat16", 1.0, NORM_SPREAD),
        (p + "ffn_norm", (h,), "bfloat16", 1.0, NORM_SPREAD),
        (p + "attn.wq", (h, heads * d), "bfloat16", 0.0,
         _linear(h, QK_GAIN)),
        (p + "attn.wk", (h, kv * d), "bfloat16", 0.0, _linear(h, QK_GAIN)),
        (p + "attn.wv", (h, kv * d), "bfloat16", 0.0, _linear(h)),
        (p + "attn.wg", (h, heads), "bfloat16", 0.0, _linear(h)),
        (p + "attn.wo", (heads * d, h), "bfloat16", 0.0,
         _linear(heads * d))]
    if bb["mlp_layer_types"][i] == "dense":
        m = int(bb["intermediate_size"])
        return out + [
            (p + "ffn.w1", (h, m), "bfloat16", 0.0, _linear(h)),
            (p + "ffn.w3", (h, m), "bfloat16", 0.0, _linear(h)),
            (p + "ffn.w2", (m, h), "bfloat16", 0.0,
             _linear(m, FFN_OUT_GAIN))]
    m, s = (int(bb["moe_intermediate_size"]),
            int(bb["shared_expert_intermediate_size"]))
    held = int(bb["num_experts"])
    routed = int((bb.get("expert_parallel") or {}).get("routed_experts",
                                                       held))
    return out + [
        (p + "ffn.router", (h, routed), "bfloat16", 0.0,
         _linear(h, ROUTER_GAIN)),
        (p + "ffn.w1", (held, h, m), "bfloat16", 0.0, _linear(h)),
        (p + "ffn.w3", (held, h, m), "bfloat16", 0.0, _linear(h)),
        (p + "ffn.w2", (held, m, h), "bfloat16", 0.0,
         _linear(m, EXPERT_OUT_GAIN)),
        (p + "ffn.shared_w1", (h, s), "bfloat16", 0.0, _linear(h)),
        (p + "ffn.shared_w3", (h, s), "bfloat16", 0.0, _linear(h)),
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
           ("norm_f", (h,), "bfloat16", 1.0, NORM_SPREAD),
           ("unit_table", (v, latent), "float32", 0.0, math.sqrt(3.0))]
    for i in range(int(bb["num_hidden_layers"])):
        out += layer_specs(bb, i)
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
    """Layer ``i`` as the reference names it: the two norms, ``attn`` and
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
