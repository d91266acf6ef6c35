"""Operations and bytes a Nemotron-H step program needs, counted from the
configuration's sizes alone (``run["dims"]["backbone"]``), for the roofline
of ``device.step_roofline.hybrid``.  The count is of the mathematics at the
published widths, whatever implements it: an expert is ``2 x hidden x 1856``
parameters though its matrices lie in 1920 lanes, and the state update is
counted the same whether XLA or a kernel runs it.

One step feeds one token of each live row through every layer.

Bytes, the least a step can move: the weights of the *held* experts that
were touched (each once, whatever the number of rows that chose it), every
other weight once (the Mamba layers' projections, convolution and vectors,
attention, routers at their published width, the shared experts, norms,
the head's own matrix; of the embedding only the rows looked up, which are
left out), the recurrent state and the convolution columns of every live
row read and written (float32: they do not grow with the row), the keys and
values of the positions the live rows attend over, and the logits written
(float32, vocabulary a live row: the program returns them).  Weights and
the key-value cache are bfloat16 (2 bytes).  Other activations are left out.

Operations: two per multiply-accumulate of every product a token goes
through (its layer's projections, the router, its held experts, the shared
expert, the head, attention over its context) and six per element of the
recurrent state (decay, add, the product with ``C``), times the live rows.

At the cell's shapes (256 rows, all 64 held experts of each of 4 layers
touched) the bound is bytes: a step moves some 11 GB, half of it expert
weights and two fifths recurrent state, for 1.2 TFLOP.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
KV_BYTES = 2
STATE_BYTES = 4
LOGIT_BYTES = 4


def sizes(bb: dict) -> dict:
    """Parameters of each part of the backbone, and what a row holds."""
    h, d = int(bb["hidden_size"]), int(bb["head_dim"])
    heads, kv = int(bb["num_attention_heads"]), int(bb["num_key_value_heads"])
    m_heads, m_dim = int(bb["mamba_num_heads"]), int(bb["mamba_head_dim"])
    n, k = int(bb["ssm_state_size"]), int(bb["conv_kernel"])
    inner = m_heads * m_dim
    conv = inner + 2 * int(bb["n_groups"]) * n
    pattern = bb["hybrid_override_pattern"][:int(bb["num_hidden_layers"])]
    share = bb.get("expert_parallel") or {}
    return {
        "mamba": h * (inner + conv + m_heads) + (k + 1) * conv + 3 * m_heads
        + inner + inner * h,
        "attn": h * (heads + 2 * kv) * d + heads * d * h,
        "expert": 2 * h * int(bb["moe_intermediate_size"]),
        "shared": 2 * h * int(bb["moe_shared_expert_intermediate_size"]),
        "router": (h + 1) * int(share.get("routed_experts",
                                          bb["n_routed_experts"])),
        "norm": h,
        "head": int(bb["vocab_size"]) * h,
        "mamba_layers": pattern.count("M"),
        "attn_layers": pattern.count("*"),
        "expert_layers": pattern.count("E"),
        "state": inner * n + (k - 1) * conv,
        "kv_width": kv * d,
        "q_width": heads * d,
        "vocab": int(bb["vocab_size"]),
    }


def step_cost(bb: dict, live_rows: float, held_experts_touched: float,
              held_assignments: float, kv_positions: float) -> dict:
    """``{"ops", "bytes", "state_bytes", "expert_bytes"}`` of one step.
    ``held_experts_touched``: distinct held experts chosen, summed over the
    expert layers; ``held_assignments``: the assignments that fell on them,
    likewise; ``kv_positions``: positions attended over, summed over the
    live rows."""
    z = sizes(bb)
    layers = z["mamba_layers"] + z["attn_layers"] + z["expert_layers"]
    fixed = (z["mamba_layers"] * z["mamba"] + z["attn_layers"] * z["attn"]
             + z["expert_layers"] * (z["router"] + z["shared"])
             + (layers + 1) * z["norm"] + z["head"])
    expert_bytes = WEIGHT_BYTES * held_experts_touched * z["expert"]
    state_bytes = 2 * STATE_BYTES * z["mamba_layers"] * z["state"] * live_rows
    moved = WEIGHT_BYTES * fixed + expert_bytes + state_bytes \
        + KV_BYTES * 2 * z["attn_layers"] * z["kv_width"] * kv_positions \
        + LOGIT_BYTES * live_rows * z["vocab"]
    active = (z["mamba_layers"] * z["mamba"] + z["attn_layers"] * z["attn"]
              + z["expert_layers"] * (z["router"] + z["shared"])
              + z["head"])
    ops = 2.0 * active * live_rows + 2.0 * z["expert"] * held_assignments \
        + 6.0 * z["mamba_layers"] * z["state"] * live_rows \
        + 2.0 * 2 * z["attn_layers"] * z["q_width"] * kv_positions
    return {"ops": ops, "bytes": float(moved),
            "state_bytes": float(state_bytes),
            "expert_bytes": float(expert_bytes)}
