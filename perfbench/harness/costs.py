"""Operations and bytes the VITS graph needs for one dispatched shape, and
the table of peaks.  Counted from the configuration's sizes alone.

Operations: two per multiply-accumulate of every convolution and attention
product, at the padded shape ``(b, t, f)`` the device really ran.  Bytes:
the weights once, and for every convolution its input read and its output
written once in float32 — the least a layer-by-layer execution can move; a
fused program may move less, an unfused one moves more.  The roofline time
is the larger of operations over the peak rate and bytes over the peak
bandwidth; the function says which of the two bounds.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


def _conv(k, c_in, c_out, length, groups=1):
    ops = 2.0 * k * (c_in // groups) * c_out * length
    moved = 4.0 * length * (c_in + c_out)
    weights = 4.0 * k * (c_in // groups) * c_out
    return ops, moved, weights


def full_fn_cost(dims: dict, b: int, t: int, f: int,
                 speakers: int = 1) -> dict:
    """Operations and bytes of one full pipeline dispatch at ``(b, t, f)``:
    text encoder, duration predictor, length regulation, flow, generator."""
    h, inter, filt = (dims["hidden_channels"], dims["inter_channels"],
                      dims["filter_channels"])
    total = [0.0, 0.0, 0.0]

    def add(cost, times=1):
        for i in range(3):
            total[i] += cost[i] * times

    # text encoder over t ids
    for _ in range(dims["n_layers"]):
        add(_conv(1, h, h, t), 4)                        # q, k, v, o
        add((2.0 * 2 * t * t * h, 4.0 * 3 * t * h, 0.0))  # scores, mix
        add(_conv(dims["kernel_size"], h, filt, t))
        add(_conv(dims["kernel_size"], filt, h, t))
    add(_conv(1, h, 2 * inter, t))
    # duration predictor: the conditioning stack and the reversed flows
    dpf, dpk = dims["dp_filter_channels"], dims["dp_kernel_size"]

    def dds():
        for _ in range(3):
            add(_conv(dpk, dpf, dpf, t, groups=dpf))
            add(_conv(1, dpf, dpf, t))

    add(_conv(1, h, dpf, t))
    dds()
    add(_conv(1, dpf, dpf, t))
    for _ in range(dims["dp_n_flows"] - 1):
        add(_conv(1, 1, dpf, t))
        dds()
        add(_conv(1, dpf, 3 * dims["dp_num_bins"] - 1, t))
    # length regulation: two [t, f] x [t, inter] products
    add((2.0 * 2 * t * f * inter, 4.0 * 2 * (t * inter + f * inter), 0.0))
    # flow over f frames
    half = inter // 2
    for _ in range(dims["flow_n_layers"]):
        add(_conv(1, half, h, f))
        for j in range(dims["flow_wn_layers"]):
            add(_conv(dims["flow_kernel_size"], h, 2 * h, f))
            add(_conv(1, h, 2 * h if j < dims["flow_wn_layers"] - 1 else h,
                      f))
        add(_conv(1, h, half, f))
    # generator
    ch = dims["upsample_initial_channel"]
    add(_conv(7, inter, ch, f))
    length = f
    for rate, k in zip(dims["upsample_rates"],
                       dims["upsample_kernel_sizes"]):
        length *= rate
        # a transposed conv gives each output k / rate taps
        ops, _, weights = _conv(k, ch, ch // 2, length)
        add((ops / rate, 4.0 * (length // rate * ch + length * ch // 2),
             weights))
        ch //= 2
        for kr, dils in zip(dims["resblock_kernel_sizes"],
                            dims["resblock_dilation_sizes"]):
            add(_conv(kr, ch, ch, length), 2 * len(dils))
    add(_conv(7, ch, 1, length))
    ops, moved, weights = total
    if speakers > 1:
        weights += 4.0 * speakers * dims["gin_channels"]
    return {"ops": ops * b, "bytes": moved * b + weights,
            "samples": length * b}


def roofline(cost: dict, peak: dict) -> dict:
    t_ops = cost["ops"] / peak["flops_per_s"]
    t_bytes = cost["bytes"] / peak["bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes"}
