"""The unit-LM voices' writer: a voice of the ``unit_lm`` family from a seed.

``write_voice`` leaves, under ``out_dir``: ``voice.json`` (the voice JSON
the server is started with: the configuration's ``voice`` block, its
``backbone`` filled from the configuration's published keys, its ``units``),
``generator.npz`` (the HiFi-GAN generator's float32 weights, the ``dec/``
keys that ``voicegen.build_params`` draws for the same ``voice.model``) and
``recipe.json`` (the seed).  The backbone's weights do not cross a disk: they
are a *recipe*, ``draw(config, name)``, that the configuration's server
command (``perfbench/harness/lfm2_serve.py``) expands on the device layer by
layer, and that the comparison expands again for the reference, so both read
the same bfloat16 numbers and neither made them.

The recipe: the ``n``-th tensor of ``tensor_specs`` is ``centre + bound *
uniform(-1, 1)`` drawn in float32 from ``fold_in(PRNGKey(seed), n)``
(threefry bits to floats by the mantissa, no transcendental: the same bits on
every backend) and rounded to its storage type.  Bounds are variance
preserving (``sqrt(3 / fan_in)`` times a gain), so that every stage carries
signal at the published widths; the embedding is scaled so that the tied
head's logits have a spread of about ``LOGIT_STD``; ``expert_bias`` is
non-zero, so that the experts chosen and their weights differ.

Nothing here imports jax while the module is loaded (``run.py`` loads it).
"""

from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import numpy as np

from perfbench.harness import voicegen

#: the configuration's top-level keys that are the backbone's published
#: ``config.json`` (they go into the voice JSON's ``backbone`` block as they
#: stand)
BACKBONE_KEYS = (
    "model_type", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "rope_parameters", "conv_L_cache", "conv_bias",
    "intermediate_size", "moe_intermediate_size", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
    "routed_scaling_factor", "num_dense_layers", "norm_eps", "vocab_size",
    "num_hidden_layers", "layer_types", "max_position_embeddings")

LOGIT_STD = 3.0
NORM_SPREAD = 0.1
EXPERT_BIAS_BOUND = 0.05
FFN_OUT_GAIN = 1.4
EXPERT_OUT_GAIN = 2.0


def backbone(config: dict) -> dict:
    return {k: config[k] for k in BACKBONE_KEYS}


def voice_json(config: dict) -> dict:
    return dict(config["voice"], backbone=backbone(config))


def _linear(fan_in: int, gain: float = 1.0) -> float:
    return gain * math.sqrt(3.0 / fan_in)


def layer_specs(bb: dict, i: int) -> list:
    """``(name, shape, storage type, centre, bound)`` of layer ``i``'s
    tensors, under the reference's names."""
    h = int(bb["hidden_size"])
    heads, kv = int(bb["num_attention_heads"]), int(bb["num_key_value_heads"])
    d = h // heads
    p = f"layers.{i}."
    out = [(p + "op_norm", (h,), "bfloat16", 1.0, NORM_SPREAD),
           (p + "ffn_norm", (h,), "bfloat16", 1.0, NORM_SPREAD)]
    if bb["layer_types"][i] == "conv":
        k = int(bb["conv_L_cache"])
        out += [(p + "op.in_proj", (h, 3 * h), "bfloat16", 0.0, _linear(h)),
                (p + "op.conv_w", (k, h), "bfloat16", 0.0, _linear(k)),
                (p + "op.out_proj", (h, h), "bfloat16", 0.0, _linear(h))]
    else:
        out += [(p + "op.wq", (h, heads * d), "bfloat16", 0.0, _linear(h)),
                (p + "op.wk", (h, kv * d), "bfloat16", 0.0, _linear(h)),
                (p + "op.wv", (h, kv * d), "bfloat16", 0.0, _linear(h)),
                (p + "op.wo", (heads * d, h), "bfloat16", 0.0,
                 _linear(heads * d)),
                (p + "op.q_norm", (d,), "bfloat16", 1.0, NORM_SPREAD),
                (p + "op.k_norm", (d,), "bfloat16", 1.0, NORM_SPREAD)]
    if i < int(bb["num_dense_layers"]):
        m = int(bb["intermediate_size"])
        out += [(p + "ffn.w1", (h, m), "bfloat16", 0.0, _linear(h)),
                (p + "ffn.w3", (h, m), "bfloat16", 0.0, _linear(h)),
                (p + "ffn.w2", (m, h), "bfloat16", 0.0,
                 _linear(m, FFN_OUT_GAIN))]
    else:
        m, e = int(bb["moe_intermediate_size"]), int(bb["num_experts"])
        out += [(p + "ffn.router", (h, e), "bfloat16", 0.0, _linear(h)),
                (p + "ffn.expert_bias", (e,), "float32", 0.0,
                 EXPERT_BIAS_BOUND),
                (p + "ffn.w1", (e, h, m), "bfloat16", 0.0, _linear(h)),
                (p + "ffn.w3", (e, h, m), "bfloat16", 0.0, _linear(h)),
                (p + "ffn.w2", (e, m, h), "bfloat16", 0.0,
                 _linear(m, EXPERT_OUT_GAIN))]
    return out


def tensor_specs(config: dict) -> list:
    """Every tensor of the voice but the generator's, in the recipe's
    order (a tensor's place in this list is part of its key)."""
    bb = backbone(config)
    h, v = int(bb["hidden_size"]), int(bb["vocab_size"])
    latent = int(voicegen.model_dims(config["voice"])["inter_channels"])
    out = [("embed", (v, h), "bfloat16", 0.0,
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
    return _maker(*specs[n][1:])(key)


@functools.lru_cache(maxsize=None)
def _maker(shape: tuple, dtype: str, centre: float, bound: float):
    """The jitted draw of one shape (layers share shapes: compiled once)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
        return (centre + bound * u).astype(getattr(jnp, dtype))

    return make


def nest(flat: dict) -> dict:
    """``{"a.b": x}`` to ``{"a": {"b": x}}``."""
    out: dict = {}
    for name, value in flat.items():
        node = out
        keys = name.split(".")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = value
    return out


def draw_layer(config: dict, i: int) -> dict:
    """Layer ``i`` as the reference names it: ``{"op_norm", "ffn_norm",
    "op": {...}, "ffn": {...}}``, storage types as drawn."""
    prefix = f"layers.{i}."
    return nest({s[0][len(prefix):]: draw(config, s[0])
                 for s in layer_specs(backbone(config), i)})


def generator_flat(config: dict) -> dict:
    flat = voicegen.build_params(
        config["voice"], seed=int(config["weights"]["seed"]))
    return {k: v for k, v in flat.items() if k.startswith("dec/")}


def reference_params(config: dict):
    """The generator's parameters as ``vits_ref.generator`` takes them."""
    return voicegen.unflatten(generator_flat(config))["dec"]


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
    product's own loader reads (``<name>.bf16.npy`` holds the bit patterns
    as uint16, ``<name>.f32.npy`` float32).  For sizes that fit a disk: the
    tests' tiny voice."""
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
