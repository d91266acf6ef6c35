"""Operations and bytes an openPangu-Ultra-MoE step program needs, counted
from the configuration's sizes alone (``run["dims"]["backbone"]``), for the
rooflines of ``device.step_roofline.latent`` and
``mla.attention_roofline.latent``.  The count is of the mathematics at the
published widths, whatever implements it: a cached row is 576 values
though it lies in 640 lanes, and latent attention is counted in the
absorbed form a step runs (per position and head one product over the
row's 576 values for the score and one over its 512 for the value).

One step feeds one token of each live row through every layer.

Bytes, the least a step can move: the weights of the *held* experts that
were touched (each once, whatever the number of rows that chose it), every
other weight once (latent attention's five matrices and two norms, the
dense feed-forward, routers at their published width, the shared experts,
the four norms a layer, the head over the rows of the vocabulary held
here; of the embedding only the rows looked up, which are left out), the
latent rows of the positions the live rows attend over (once: keys and
values at once) and the logits written (float32, held vocabulary a live
row: the program returns them).  Weights and the cache are bfloat16 (2
bytes).  Other activations are left out.

Operations: two per multiply-accumulate of every product a token goes
through (its layer's projections, ``W_kvb`` folded into the query and out
of the result, the router, its held experts, the shared expert, the dense
feed-forward, the head) and of attention over its context, times the live
rows.

At the cell's shapes (256 rows, the 8 held experts of each of 6 layers
touched) the bound is bytes with the operations over half of it: a step
moves some 9.5 GB, 11.6 ms, for 1.3 TFLOP, 6.8 ms.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
CACHE_BYTES = 2
LOGIT_BYTES = 4


def sizes(bb: dict) -> dict:
    """Parameters of each part of the backbone, and a cached row's
    widths."""
    h, heads = int(bb["hidden_size"]), int(bb["num_attention_heads"])
    q_rank, c = int(bb["q_lora_rank"]), int(bb["kv_lora_rank"])
    nope, rope, v = (int(bb["qk_nope_head_dim"]), int(bb["qk_rope_head_dim"]),
                     int(bb["v_head_dim"]))
    layers, dense = (int(bb["num_hidden_layers"]),
                     int(bb["first_k_dense_replace"]))
    share = bb.get("expert_parallel") or {}
    expert = 3 * h * int(bb["moe_intermediate_size"])
    return {
        "mla": h * q_rank + q_rank + q_rank * heads * (nope + rope)
        + h * (c + rope) + c + c * heads * (nope + v) + heads * v * h,
        "dense": 3 * h * int(bb["intermediate_size"]),
        "expert": expert,
        "shared": expert * int(bb["n_shared_experts"]),
        "router": h * int(share.get("routed_experts",
                                    bb["n_routed_experts"])),
        "norms": 4 * h,
        "head": int(bb["vocab_size"]) * h + h,
        "layers": layers, "dense_layers": dense,
        "expert_layers": layers - dense,
        "heads": heads, "row": c + rope, "values": c,
        "vocab": int(bb["vocab_size"]),
    }


def attention_cost(bb: dict, live_rows: float, kv_positions: float) -> dict:
    """``{"ops", "bytes"}`` of one layer's read of the latent cache in one
    step: ``kv_positions`` rows (summed over the live rows) once, the live
    rows' queries (bfloat16, every head a row's width) in and their results
    (float32, every head the values' width) out; per position and head a
    product over the row and one over its values."""
    z = sizes(bb)
    moved = CACHE_BYTES * z["row"] * kv_positions \
        + live_rows * z["heads"] * (CACHE_BYTES * z["row"] + 4 * z["values"])
    ops = 2.0 * z["heads"] * (z["row"] + z["values"]) * kv_positions
    return {"ops": ops, "bytes": float(moved)}


def step_cost(bb: dict, live_rows: float, held_experts_touched: float,
              held_assignments: float, kv_positions: float) -> dict:
    """``{"ops", "bytes", "latent_bytes", "expert_bytes"}`` of one step.
    ``held_experts_touched``: distinct held experts chosen, summed over the
    expert layers; ``held_assignments``: the assignments that fell on them,
    likewise; ``kv_positions``: positions attended over, summed over the
    live rows."""
    z = sizes(bb)
    fixed = (z["layers"] * (z["mla"] + z["norms"])
             + z["dense_layers"] * z["dense"]
             + z["expert_layers"] * (z["router"] + z["shared"]) + z["head"])
    expert_bytes = WEIGHT_BYTES * held_experts_touched * z["expert"]
    latent_bytes = CACHE_BYTES * z["layers"] * z["row"] * kv_positions
    moved = WEIGHT_BYTES * fixed + expert_bytes + latent_bytes \
        + LOGIT_BYTES * live_rows * z["vocab"]
    ops = 2.0 * fixed * live_rows + 2.0 * z["expert"] * held_assignments \
        + z["layers"] * attention_cost(bb, live_rows, kv_positions)["ops"]
    return {"ops": ops, "bytes": float(moved),
            "latent_bytes": float(latent_bytes),
            "expert_bytes": float(expert_bytes)}
