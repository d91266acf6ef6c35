"""Operations and bytes an LFM2-MoE step program needs, counted from the
configuration's sizes alone (``run["dims"]["backbone"]``), for the
roofline of ``device.step_roofline.sentence``.

One step feeds one token of each live row through every layer.

Bytes, the least a step can move: the held weights of the experts that
were *touched* (each once, whatever the number of rows that chose it),
every other weight once (operators, the dense layers' feed-forward, routers,
norms, and the embedding, which the tied head reads whole), and the keys
and values of the positions the live rows attend over.  Weights and the
key-value cache are bfloat16 (2 bytes).  Activations are left out: at 64
rows they are under a thousandth of the weights.

Operations: two per multiply-accumulate of every product a token goes
through (its operator, its feed-forward or its ``num_experts_per_tok``
experts, the router, the head, attention over its context), times the
live rows.

At the cell's shapes (64 rows, 64 experts of which about 60 are touched in
each of 8 layers) the bound is bytes: a step streams some ten gigabytes of
weights for a fifth of a TFLOP.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
KV_BYTES = 2


def sizes(bb: dict) -> dict:
    """Parameters of each part of the backbone."""
    h = int(bb["hidden_size"])
    heads, kv = int(bb["num_attention_heads"]), int(bb["num_key_value_heads"])
    d = h // heads
    kinds = list(bb["layer_types"])[:int(bb["num_hidden_layers"])]
    dense = int(bb["num_dense_layers"])
    return {
        "conv_op": h * 3 * h + int(bb["conv_L_cache"]) * h + h * h,
        "attn_op": h * (heads + 2 * kv) * d + heads * d * h + 2 * d,
        "dense_ffn": 3 * h * int(bb["intermediate_size"]),
        "expert": 3 * h * int(bb["moe_intermediate_size"]),
        "router": h * int(bb["num_experts"]) + int(bb["num_experts"]),
        "norms": 2 * h,
        "embed": int(bb["vocab_size"]) * h,
        "final_norm": h,
        "conv_layers": kinds.count("conv"),
        "attn_layers": kinds.count("full_attention"),
        "dense_layers": dense,
        "expert_layers": len(kinds) - dense,
        "kv_width": kv * d,
        "top_k": int(bb["num_experts_per_tok"]),
    }


def step_cost(bb: dict, live_rows: float, experts_touched: float,
              kv_positions: float) -> dict:
    """``{"ops", "bytes"}`` of one step.  ``experts_touched``: distinct
    experts chosen, summed over the expert layers; ``kv_positions``:
    positions attended over, summed over the live rows (each attention
    layer reads a key and a value of ``kv_width`` for every one)."""
    z = sizes(bb)
    layers = z["conv_layers"] + z["attn_layers"]
    fixed = (z["conv_layers"] * z["conv_op"] + z["attn_layers"] * z["attn_op"]
             + z["dense_layers"] * z["dense_ffn"]
             + z["expert_layers"] * z["router"] + layers * z["norms"]
             + z["embed"] + z["final_norm"])
    moved = WEIGHT_BYTES * (fixed + experts_touched * z["expert"]) \
        + KV_BYTES * 2 * z["attn_layers"] * z["kv_width"] * kv_positions
    active = (z["conv_layers"] * z["conv_op"]
              + z["attn_layers"] * z["attn_op"]
              + z["dense_layers"] * z["dense_ffn"]
              + z["expert_layers"] * (z["router"]
                                      + z["top_k"] * z["expert"])
              + z["embed"])
    heads_width = int(bb["hidden_size"])
    ops = 2.0 * active * live_rows \
        + 2.0 * 2 * z["attn_layers"] * heads_width * kv_positions
    return {"ops": ops, "bytes": float(moved)}
