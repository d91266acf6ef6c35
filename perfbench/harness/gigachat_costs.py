"""Operations and bytes a GigaChat-3.5 step program needs, counted from the
configuration's sizes alone (``run["dims"]["backbone"]``), for the rooflines
of ``device.step_roofline.delta``, ``delta.update_roofline.delta`` and
``mla.attention_roofline.delta``.  The count is of the mathematics at the
published widths, whatever implements it: a delta-rule state is read once
and written once a step though today's program reads it twice, a cached
latent row is 576 values though it lies in 640 lanes, and latent attention
is counted in the absorbed form a step runs (``pangu_costs``' count at this
geometry: one layer of 64 heads).

One step feeds one token of each live row through every layer.

Bytes, the least a step can move: the weights of the *held* experts that
were touched (each once, whatever the number of rows that chose it), every
other weight once (the linear mixers' projections, convolution and vectors,
latent attention's six matrices and two norms, the dense feed-forward,
routers at their published width with their bias, the shared experts, the
four norms a layer, the head over the rows of the vocabulary held here; of
the embedding only the rows looked up, which are left out), the delta-rule
state and the convolution columns of every live row read and written
(float32: they do not grow with the row), the latent rows of the positions
the live rows attend over (once: keys and values at once) and the logits
written (float32, held vocabulary a live row: the program returns them).
Weights and the latent cache are bfloat16 (2 bytes).  Other activations are
left out.

Operations: two per multiply-accumulate of every product a token goes
through (its layer's projections, ``W_kvb`` folded into the query and out
of the result, the router, its held experts, the shared expert, the dense
feed-forward, the head) and of latent attention over its context, and seven
per element of a delta-rule state (two reductions, the decay and the
rank-one update), times the live rows.

At the cell's shapes (256 rows, the 8 held experts of each of 4 layers
touched) the bound is bytes: a step moves some 15.5 GB, three fifths of it
delta-rule state, for 1.8 TFLOP.
"""

from __future__ import annotations

from perfbench.harness import pangu_costs

WEIGHT_BYTES = 2
CACHE_BYTES = 2
STATE_BYTES = 4
LOGIT_BYTES = 4
#: operations an element of a state costs a step: ``S^T k`` and ``S^T q``
#: (a multiply and an add each), the decay, the rank-one update's multiply
#: and its add
STATE_OPS = 7.0


def sizes(bb: dict) -> dict:
    """Parameters of each part of the backbone, and what a row holds."""
    z = pangu_costs.sizes(bb)
    h = int(bb["hidden_size"])
    kh, vh = int(bb["linear_num_key_heads"]), int(
        bb["linear_num_value_heads"])
    dk, dv = int(bb["linear_key_head_dim"]), int(bb["linear_value_head_dim"])
    taps, conv = int(bb["linear_conv_kernel_dim"]), 2 * kh * dk + vh * dv
    layers = int(bb["num_hidden_layers"])
    full = len([i for i in bb["full_attention_layers"] if i < layers])
    return dict(
        z,
        # latent attention with its gate's matrix
        mla=z["mla"] + h * z["heads"] * int(bb["v_head_dim"]),
        linear=h * (conv + vh * dv + 2 * vh) + taps * conv + 2 * vh + dv
        + vh * dv * h,
        # the router's correction bias (a number an output) beside its
        # matrix
        router=z["router"] * (h + 1) // h,
        full_layers=full, linear_layers=layers - full,
        state=vh * dk * dv, columns=(taps - 1) * conv)


def attention_cost(bb: dict, live_rows: float, kv_positions: float) -> dict:
    """``{"ops", "bytes"}`` of the full layer's read of the latent cache in
    one step: ``pangu_costs.attention_cost`` at this geometry."""
    return pangu_costs.attention_cost(bb, live_rows, kv_positions)


def update_cost(bb: dict, live_rows: float) -> dict:
    """``{"ops", "bytes"}`` of one linear layer's state update in one step:
    every live row's state once in and once out."""
    z = sizes(bb)
    return {"ops": STATE_OPS * z["state"] * live_rows,
            "bytes": float(2 * STATE_BYTES * z["state"] * live_rows)}


def step_cost(bb: dict, live_rows: float, held_experts_touched: float,
              held_assignments: float, kv_positions: float) -> dict:
    """``{"ops", "bytes", "state_bytes", "latent_bytes", "expert_bytes"}``
    of one step.  ``held_experts_touched``: distinct held experts chosen,
    summed over the expert layers; ``held_assignments``: the assignments
    that fell on them, likewise; ``kv_positions``: positions attended over,
    summed over the live rows."""
    z = sizes(bb)
    fixed = (z["linear_layers"] * z["linear"] + z["full_layers"] * z["mla"]
             + z["layers"] * z["norms"] + z["dense_layers"] * z["dense"]
             + z["expert_layers"] * (z["router"] + z["shared"]) + z["head"])
    expert_bytes = WEIGHT_BYTES * held_experts_touched * z["expert"]
    state_bytes = 2 * STATE_BYTES * z["linear_layers"] * (
        z["state"] + z["columns"]) * live_rows
    latent_bytes = CACHE_BYTES * z["full_layers"] * z["row"] * kv_positions
    moved = WEIGHT_BYTES * fixed + expert_bytes + state_bytes + latent_bytes \
        + LOGIT_BYTES * live_rows * z["vocab"]
    ops = 2.0 * fixed * live_rows + 2.0 * z["expert"] * held_assignments \
        + z["linear_layers"] * update_cost(bb, live_rows)["ops"] \
        + z["full_layers"] * attention_cost(bb, live_rows,
                                            kv_positions)["ops"]
    return {"ops": ops, "bytes": float(moved),
            "state_bytes": float(state_bytes),
            "latent_bytes": float(latent_bytes),
            "expert_bytes": float(expert_bytes)}
