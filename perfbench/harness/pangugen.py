"""The openPangu-Ultra-MoE unit voices' writer: a voice of the ``unit_lm``
family with a ``pangu_ultra_moe`` backbone, from a seed.  Its configuration
lives under ``perfbench/configs/pangu/`` (a file a configuration: the
published ``config.json`` keys at the top level, ``expert_parallel`` and
``vocab_parallel`` for the chip's share, ``reduced`` / ``published`` /
``deployment`` / ``assumed`` / ``precision`` / ``memory`` in prose, the
server's command and the ``voice`` block).  ``lfm2gen.py``'s way:
``write_voice`` leaves ``voice.json``, ``generator.npz`` and ``recipe.json``
(the seed); the backbone's weights are a *recipe*, ``draw(config, name)``,
that the configuration's server command (``perfbench/harness/pangu_serve.py``)
expands on the device layer by layer and the comparison expands again for
the reference, so both read the same bfloat16 numbers and neither made them.

The recipe is ``lfm2gen``'s (the ``n``-th tensor of ``tensor_specs`` is
``centre + bound * uniform(-1, 1)`` from ``fold_in(PRNGKey(seed), n)``,
rounded to its storage type; bounds variance preserving; the embedding at
unit variance and the head, a matrix of its own, scaled so that the logits
spread by about ``LOGIT_STD``, as ``sdargen``).  What this family adds:

- **the four norms of a layer are not alike** (``NORM_CENTRES``: gains
  about 1.0, 0.6, 1.2 and 0.8, each ``+- NORM_SPREAD``), nor the two inside
  the attention (1.0 and 0.9), so that a norm dropped or swapped for its
  neighbour moves the logits;
- the router is drawn at its published width
  (``expert_parallel.routed_experts``) whatever share of the experts the
  chip holds (``n_routed_experts``, the held experts' tensors),
  ``ROUTER_GAIN`` times wider than variance preserving (its sigmoids then
  spread over most of (0, 1) and the eighth and ninth of 256 lie further
  apart than rounding moves them, mostly), and has no bias;
- embedding, head and unit table have the rows of the vocabulary held here
  (``vocab_size``; ``vocab_parallel`` states the published count).

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
    "model_type", "hidden_size", "num_hidden_layers",
    "first_k_dense_replace", "num_attention_heads", "num_key_value_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "attention_bias", "rope_theta", "hidden_act",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "rms_norm_eps", "sandwich_norm",
    "num_nextn_predict_layers", "vocab_size", "max_position_embeddings",
    "tie_word_embeddings", "expert_parallel", "vocab_parallel")

#: a layer's four norms, by name, and the centre each gain is drawn about
NORM_CENTRES = {"input_norm": 1.0, "post_attn_norm": 0.6,
                "pre_mlp_norm": 1.2, "post_mlp_norm": 0.8}
Q_NORM_CENTRE, KV_NORM_CENTRE = 1.0, 0.9
ROUTER_GAIN = 2.0


def backbone(config: dict) -> dict:
    return {k: config[k] for k in BACKBONE_KEYS}


def voice_json(config: dict) -> dict:
    return dict(config["voice"], backbone=backbone(config))


def layer_specs(bb: dict, i: int) -> list:
    """``(name, shape, storage type, centre, bound)`` of layer ``i``'s
    tensors, under the reference's names."""
    h, heads = int(bb["hidden_size"]), int(bb["num_attention_heads"])
    q_rank, c = int(bb["q_lora_rank"]), int(bb["kv_lora_rank"])
    nope, rope, v = (int(bb["qk_nope_head_dim"]), int(bb["qk_rope_head_dim"]),
                     int(bb["v_head_dim"]))
    p = f"layers.{i}."
    out = [(p + name, (h,), "bfloat16", centre, NORM_SPREAD)
           for name, centre in NORM_CENTRES.items()]
    out += [
        (p + "attn.wq_a", (h, q_rank), "bfloat16", 0.0, _linear(h)),
        (p + "attn.q_norm", (q_rank,), "bfloat16", Q_NORM_CENTRE,
         NORM_SPREAD),
        (p + "attn.wq_b", (q_rank, heads * (nope + rope)), "bfloat16", 0.0,
         _linear(q_rank)),
        (p + "attn.wkv_a", (h, c + rope), "bfloat16", 0.0, _linear(h)),
        (p + "attn.kv_norm", (c,), "bfloat16", KV_NORM_CENTRE, NORM_SPREAD),
        (p + "attn.wkv_b", (c, heads * (nope + v)), "bfloat16", 0.0,
         _linear(c)),
        (p + "attn.wo", (heads * v, h), "bfloat16", 0.0,
         _linear(heads * v))]
    if i < int(bb["first_k_dense_replace"]):
        m = int(bb["intermediate_size"])
        return out + [
            (p + "ffn.w1", (h, m), "bfloat16", 0.0, _linear(h)),
            (p + "ffn.w3", (h, m), "bfloat16", 0.0, _linear(h)),
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
    """Layer ``i`` as the reference names it: the four norms, ``attn`` and
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
