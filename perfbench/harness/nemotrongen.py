"""The Nemotron-H unit voices' writer: a voice of the ``unit_lm`` family
with a ``nemotron_h`` backbone, from a seed.  ``lfm2gen.py``'s way:
``write_voice`` leaves ``voice.json``, ``generator.npz`` and ``recipe.json``
(the seed); the backbone's weights are a *recipe*, ``draw(config, name)``,
that the configuration's server command
(``perfbench/harness/nemotron_serve.py``) expands on the device layer by
layer and the comparison expands again for the reference, so both read the
same bfloat16 numbers and neither made them.

The recipe is ``lfm2gen``'s (the ``n``-th tensor of ``tensor_specs`` is
``centre + bound * uniform(-1, 1)`` from ``fold_in(PRNGKey(seed), n)``,
rounded to its storage type; bounds variance preserving; the embedding at
unit variance and the head, a matrix of its own, scaled so that the logits
spread by about ``LOGIT_STD``, as ``sdargen``).  What a Mamba layer adds:
``dt_bias`` around -2 and ``A_log`` around -0.5, so that ``dt`` lies about
0.03-0.3 (the published ``time_step`` range's upper decades), ``A`` about
0.3-1.2 and a state forgets over some 3 to 100 steps, tens for most heads: a
state that forgot at once, or never, would let a stale or a rounded state
pass unseen; ``D`` around 1 as published.  The router is drawn at its
published width (``expert_parallel.routed_experts``) whatever share of the
experts the chip holds (``n_routed_experts``, the held experts' tensors);
``e_score_correction_bias`` is small and non-zero, so that the experts
chosen and their weights differ.

Nothing here imports jax while the module is loaded (``run.py`` loads it).
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from perfbench.harness import lfm2gen, voicegen
from perfbench.harness.lfm2gen import LOGIT_STD, NORM_SPREAD, _linear, \
    generator_flat, nest, reference_params  # noqa: F401

#: the configuration's top-level keys that are the backbone's published
#: ``config.json``, and ``expert_parallel`` (the share of the routed experts
#: held here): they go into the voice JSON's ``backbone`` block as they stand
BACKBONE_KEYS = (
    "model_type", "hidden_size", "hybrid_override_pattern",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "attention_bias", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "conv_kernel", "chunk_size", "expand",
    "use_conv_bias", "mamba_proj_bias", "mamba_hidden_act",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "intermediate_size", "mlp_hidden_act", "mlp_bias", "use_bias",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor",
    "layer_norm_epsilon", "norm_eps", "rope_theta", "partial_rotary_factor",
    "vocab_size", "max_position_embeddings", "tie_word_embeddings",
    "expert_parallel")

EXPERT_BIAS_BOUND = 0.05
DT_BIAS = (-2.0, 1.0)
A_LOG = (-0.5, 0.7)
D_SKIP = (1.0, 0.1)


def backbone(config: dict) -> dict:
    return {k: config[k] for k in BACKBONE_KEYS}


def voice_json(config: dict) -> dict:
    return dict(config["voice"], backbone=backbone(config))


def layer_specs(bb: dict, i: int) -> list:
    """``(name, shape, storage type, centre, bound)`` of layer ``i``'s
    tensors, under the reference's names."""
    h = int(bb["hidden_size"])
    kind = bb["hybrid_override_pattern"][i]
    p = f"layers.{i}."
    out = [(p + "norm", (h,), "bfloat16", 1.0, NORM_SPREAD)]
    if kind == "M":
        heads, hp = int(bb["mamba_num_heads"]), int(bb["mamba_head_dim"])
        d = heads * hp
        c = d + 2 * int(bb["n_groups"]) * int(bb["ssm_state_size"])
        k = int(bb["conv_kernel"])
        return out + [
            (p + "mixer.in_proj", (h, d + c + heads), "bfloat16", 0.0,
             _linear(h)),
            (p + "mixer.conv_w", (k, c), "bfloat16", 0.0, _linear(k)),
            (p + "mixer.conv_b", (c,), "bfloat16", 0.0, NORM_SPREAD),
            (p + "mixer.A_log", (heads,), "float32", *A_LOG),
            (p + "mixer.D", (heads,), "float32", *D_SKIP),
            (p + "mixer.dt_bias", (heads,), "float32", *DT_BIAS),
            (p + "mixer.norm", (d,), "bfloat16", 1.0, NORM_SPREAD),
            (p + "mixer.out_proj", (d, h), "bfloat16", 0.0, _linear(d))]
    if kind == "*":
        heads, kv = int(bb["num_attention_heads"]), int(
            bb["num_key_value_heads"])
        d = int(bb["head_dim"])
        return out + [
            (p + "mixer.wq", (h, heads * d), "bfloat16", 0.0, _linear(h)),
            (p + "mixer.wk", (h, kv * d), "bfloat16", 0.0, _linear(h)),
            (p + "mixer.wv", (h, kv * d), "bfloat16", 0.0, _linear(h)),
            (p + "mixer.wo", (heads * d, h), "bfloat16", 0.0,
             _linear(heads * d))]
    m, s = int(bb["moe_intermediate_size"]), int(
        bb["moe_shared_expert_intermediate_size"])
    held = int(bb["n_routed_experts"])
    routed = int((bb.get("expert_parallel") or {}).get("routed_experts",
                                                       held))
    return out + [
        (p + "mixer.router", (h, routed), "bfloat16", 0.0, _linear(h)),
        (p + "mixer.e_score_correction_bias", (routed,), "float32", 0.0,
         EXPERT_BIAS_BOUND),
        (p + "mixer.w_up", (held, h, m), "bfloat16", 0.0, _linear(h)),
        (p + "mixer.w_down", (held, m, h), "bfloat16", 0.0, _linear(m)),
        (p + "mixer.shared_up", (h, s), "bfloat16", 0.0, _linear(h)),
        (p + "mixer.shared_down", (s, h), "bfloat16", 0.0, _linear(s))]


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
    """Layer ``i`` as the reference names it: ``{"norm", "mixer": {...}}``,
    storage types as drawn."""
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
