"""The SDAR unit voices' writer: a voice of the ``unit_lm`` family with an
``sdar_moe`` backbone, from a seed.  ``lfm2gen.py``'s way: ``write_voice``
leaves ``voice.json``, ``generator.npz`` and ``recipe.json`` (the seed); the
backbone's weights are a *recipe*, ``draw(config, name)``, that the
configuration's server command (``perfbench/harness/sdar_serve.py``) expands
on the device layer by layer and the comparison expands again for the
reference, so both read the same bfloat16 numbers and neither made them.

The recipe is ``lfm2gen``'s (the ``n``-th tensor of ``tensor_specs`` is
``centre + bound * uniform(-1, 1)`` from ``fold_in(PRNGKey(seed), n)``,
rounded to its storage type; bounds variance preserving).  What differs:
the embedding's entries have unit variance and the head, a matrix of its
own, is scaled so that the logits spread by about ``LOGIT_STD``; the router
is drawn ``ROUTER_GAIN`` times wider than variance preserving, so that its
logits spread by about 2 and the softmax over 128 experts is far from
uniform (the best expert near 0.2, the eight chosen about two thirds of
the mass, the ninth well under the eighth): a router at unit spread would
leave the top eight with a fifth of the mass and near-ties everywhere.

Nothing here imports jax while the module is loaded (``run.py`` loads it).
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from perfbench.harness import lfm2gen, voicegen
from perfbench.harness.lfm2gen import EXPERT_OUT_GAIN, LOGIT_STD, \
    NORM_SPREAD, _linear, generator_flat, nest, reference_params  # noqa: F401

#: the configuration's top-level keys that are the backbone's published
#: ``config.json`` (they go into the voice JSON's ``backbone`` block as they
#: stand)
BACKBONE_KEYS = (
    "model_type", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rope_scaling",
    "attention_bias", "hidden_act", "intermediate_size",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
    "rms_norm_eps", "vocab_size", "num_hidden_layers",
    "max_position_embeddings", "tie_word_embeddings")

ROUTER_GAIN = 2.0


def backbone(config: dict) -> dict:
    return {k: config[k] for k in BACKBONE_KEYS}


def voice_json(config: dict) -> dict:
    return dict(config["voice"], backbone=backbone(config))


def layer_specs(bb: dict, i: int) -> list:
    """``(name, shape, storage type, centre, bound)`` of layer ``i``'s
    tensors, under the reference's names."""
    h, d = int(bb["hidden_size"]), int(bb["head_dim"])
    heads, kv = int(bb["num_attention_heads"]), int(bb["num_key_value_heads"])
    m, e = int(bb["moe_intermediate_size"]), int(bb["num_experts"])
    p = f"layers.{i}."
    return [
        (p + "in_norm", (h,), "bfloat16", 1.0, NORM_SPREAD),
        (p + "post_norm", (h,), "bfloat16", 1.0, NORM_SPREAD),
        (p + "attn.wq", (h, heads * d), "bfloat16", 0.0, _linear(h)),
        (p + "attn.wk", (h, kv * d), "bfloat16", 0.0, _linear(h)),
        (p + "attn.wv", (h, kv * d), "bfloat16", 0.0, _linear(h)),
        (p + "attn.wo", (heads * d, h), "bfloat16", 0.0, _linear(heads * d)),
        (p + "attn.q_norm", (d,), "bfloat16", 1.0, NORM_SPREAD),
        (p + "attn.k_norm", (d,), "bfloat16", 1.0, NORM_SPREAD),
        (p + "moe.router", (h, e), "bfloat16", 0.0, _linear(h, ROUTER_GAIN)),
        (p + "moe.w1", (e, h, m), "bfloat16", 0.0, _linear(h)),
        (p + "moe.w3", (e, h, m), "bfloat16", 0.0, _linear(h)),
        (p + "moe.w2", (e, m, h), "bfloat16", 0.0,
         _linear(m, EXPERT_OUT_GAIN))]


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
    """Layer ``i`` as the reference names it: ``{"in_norm", "post_norm",
    "attn": {...}, "moe": {...}}``, storage types as drawn."""
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
