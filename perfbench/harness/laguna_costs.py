"""Operations and bytes a Laguna step program needs, counted from the
configuration's sizes alone (``run["dims"]["backbone"]``), for the rooflines
of ``device.step_roofline.windowed`` and ``attn.reader_roofline.windowed``.
The count is of the mathematics at the published widths, whatever
implements it: a place of a layer's cache is 8 heads of 128, keys and
values, 4096 bytes, and a read moves the places a row *holds* (every
position in a full layer, at most the window in a ring), not the tiles a
reader fetches them in.

One step feeds one token of each live row through every layer.

Bytes, the least a step can move: the weights of the *held* experts that
were touched (each once, whatever the number of rows that chose it), every
other weight once (each layer's attention with its gate, the dense
feed-forward, routers at their published width, the shared experts, the
two norms a layer, the head; of the embedding only the rows looked up,
which are left out), the keys and values of the places the live rows read
and the logits written (float32, the vocabulary a live row: the program
returns them).  Weights and the cache are bfloat16 (2 bytes).  Other
activations are left out.

Operations: two per multiply-accumulate of every product a token goes
through (its layer's projections and gate, the router, its held experts,
the shared expert, the dense feed-forward, the head) and of attention over
the places it reads (per place and query head a product over the head's
128 for the score and one for the value), times the live rows.

At the cell's shapes (256 rows, the 32 held experts of each of 7 layers
touched) the bound is bytes: a step moves some 5.6 GB, 6.9 ms, for 0.9
TFLOP, 4.6 ms; half of the bytes are the cache.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
CACHE_BYTES = 2
LOGIT_BYTES = 4
FULL, SLIDING = "full_attention", "sliding_attention"


def sizes(bb: dict) -> dict:
    """Parameters of each part of the backbone, layer counts by kind, and a
    place's bytes."""
    h, kv, d = (int(bb["hidden_size"]), int(bb["num_key_value_heads"]),
                int(bb["head_dim"]))
    layers = int(bb["num_hidden_layers"])
    heads = [int(n) for n in bb["num_attention_heads_per_layer"][:layers]]
    kinds = list(bb["layer_types"][:layers])
    mlps = list(bb["mlp_layer_types"][:layers])
    share = bb.get("expert_parallel") or {}
    return {
        # wq, wk, wv, the gate and wo, layer by layer
        "attention": [h * n * d + 2 * h * kv * d + h * n + n * d * h
                      for n in heads],
        "dense": 3 * h * int(bb["intermediate_size"]),
        "expert": 3 * h * int(bb["moe_intermediate_size"]),
        "shared": 3 * h * int(bb["shared_expert_intermediate_size"]),
        "router": h * int(share.get("routed_experts", bb["num_experts"])),
        "norms": 2 * h,
        "head": int(bb["vocab_size"]) * h + h,
        "layers": layers, "dense_layers": mlps.count("dense"),
        "expert_layers": mlps.count("sparse"),
        "full_layers": kinds.count(FULL),
        "window_layers": kinds.count(SLIDING),
        # query heads of the layers of each kind, summed
        "full_heads": sum(n for n, k in zip(heads, kinds) if k == FULL),
        "window_heads": sum(n for n, k in zip(heads, kinds) if k == SLIDING),
        "head_dim": d,
        "place_bytes": 2 * CACHE_BYTES * kv * d,
        "window": int(bb["sliding_window"]),
        "vocab": int(bb["vocab_size"]),
    }


def ring_places(bb: dict, kv_positions: float, kv_cache_bytes: float) -> float:
    """The places the live rows read in one ring layer, from what the step
    groups state: ``kv_cache_bytes`` is a place's bytes times (full layers x
    ``kv_positions`` + ring layers x these)."""
    z = sizes(bb)
    if not z["window_layers"]:
        return 0.0
    return (kv_cache_bytes / z["place_bytes"]
            - z["full_layers"] * kv_positions) / z["window_layers"]


def attention_cost(bb: dict, live_rows: float, kv_positions: float,
                   kv_cache_bytes: float) -> dict:
    """``{"ops", "bytes"}`` of all the layers' reads of their caches in one
    step: the places held (``kv_positions`` a full layer, summed over the
    live rows; :func:`ring_places` a ring layer) once, keys and values; the
    live rows' queries (bfloat16) in and their results (float32) out; per
    place and query head a product over the head's dimensions for the score
    and one for the value."""
    z = sizes(bb)
    ring = ring_places(bb, kv_positions, kv_cache_bytes)
    heads = z["full_heads"] + z["window_heads"]
    moved = float(kv_cache_bytes) + live_rows * heads * z["head_dim"] * (
        CACHE_BYTES + 4)
    ops = 2.0 * 2 * z["head_dim"] * (z["full_heads"] * kv_positions
                                     + z["window_heads"] * ring)
    return {"ops": ops, "bytes": float(moved)}


def step_cost(bb: dict, live_rows: float, held_experts_touched: float,
              held_assignments: float, kv_positions: float,
              kv_cache_bytes: float) -> dict:
    """``{"ops", "bytes", "cache_bytes", "expert_bytes"}`` of one step.
    ``held_experts_touched``: distinct held experts chosen, summed over the
    expert layers; ``held_assignments``: the assignments that fell on them,
    likewise; ``kv_positions``: positions attended over, summed over the
    live rows; ``kv_cache_bytes``: keys and values read as held, all
    layers."""
    z = sizes(bb)
    fixed = (sum(z["attention"]) + z["layers"] * z["norms"]
             + z["dense_layers"] * z["dense"]
             + z["expert_layers"] * (z["router"] + z["shared"]) + z["head"])
    expert_bytes = WEIGHT_BYTES * held_experts_touched * z["expert"]
    moved = WEIGHT_BYTES * fixed + expert_bytes + kv_cache_bytes \
        + LOGIT_BYTES * live_rows * z["vocab"]
    ops = 2.0 * fixed * live_rows + 2.0 * z["expert"] * held_assignments \
        + attention_cost(bb, live_rows, kv_positions, kv_cache_bytes)["ops"]
    return {"ops": ops, "bytes": float(moved),
            "cache_bytes": float(kv_cache_bytes),
            "expert_bytes": float(expert_bytes)}
