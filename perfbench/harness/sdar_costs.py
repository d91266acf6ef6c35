"""Operations and bytes an SDAR-MoE pass program needs, counted from the
configuration's sizes alone (``run["dims"]["backbone"]`` and the voice's
``units.block_length``), for the roofline of ``device.step_roofline.blocks``.

One pass feeds the ``block_length`` positions of each live row's current
block through every layer and the head.

Bytes, the least a pass can move: the weights of the experts that were
*touched* (each once, whatever the number of positions that chose it), every
other weight once (attention, routers, norms, the head's own matrix; of the
embedding only the rows looked up, which are left out: a thousandth), the
keys and values of the positions the live rows attend over (committed
positions and the block), and the logits written (float32, ``block_length``
x vocabulary a live row: the program returns them).  Weights and the
key-value cache are bfloat16 (2 bytes).  Other activations are left out.

Operations: two per multiply-accumulate of every product a position goes
through (attention's projections, the router, its ``num_experts_per_tok``
experts, the head, attention over its row's context), times the live rows'
positions.

At the cell's shapes (64 rows x 4 positions, all 128 experts of each of 6
layers touched) the bound is bytes: a pass streams some 8.6 GB of weights
for 1.7 TFLOP.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
KV_BYTES = 2
LOGIT_BYTES = 4


def sizes(bb: dict) -> dict:
    """Parameters of each part of the backbone."""
    h, d = int(bb["hidden_size"]), int(bb["head_dim"])
    heads, kv = int(bb["num_attention_heads"]), int(bb["num_key_value_heads"])
    return {
        "attn": h * (heads + 2 * kv) * d + heads * d * h + 2 * d,
        "expert": 3 * h * int(bb["moe_intermediate_size"]),
        "router": h * int(bb["num_experts"]),
        "norms": 2 * h,
        "head": int(bb["vocab_size"]) * h,
        "final_norm": h,
        "layers": int(bb["num_hidden_layers"]),
        "kv_width": kv * d,
        "q_width": heads * d,
        "top_k": int(bb["num_experts_per_tok"]),
        "vocab": int(bb["vocab_size"]),
    }


def pass_cost(bb: dict, block: int, live_rows: float, experts_touched: float,
              kv_positions: float) -> dict:
    """``{"ops", "bytes"}`` of one pass.  ``experts_touched``: distinct
    experts chosen, summed over the layers; ``kv_positions``: positions
    attended over (committed + the block), summed over the live rows (each
    layer reads a key and a value of ``kv_width`` for every one, and each
    of the row's ``block`` positions takes its products with them)."""
    z = sizes(bb)
    positions = live_rows * block
    fixed = z["layers"] * (z["attn"] + z["router"] + z["norms"]) \
        + z["head"] + z["final_norm"]
    moved = WEIGHT_BYTES * (fixed + experts_touched * z["expert"]) \
        + KV_BYTES * 2 * z["layers"] * z["kv_width"] * kv_positions \
        + LOGIT_BYTES * positions * z["vocab"]
    active = z["layers"] * (z["attn"] + z["router"]
                            + z["top_k"] * z["expert"]) + z["head"]
    ops = 2.0 * active * positions \
        + 2.0 * 2 * z["layers"] * z["q_width"] * kv_positions * block
    return {"ops": ops, "bytes": float(moved)}
