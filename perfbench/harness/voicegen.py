"""Voice files from a seed, in the format the server loads.

The benchmark makes its own weights (numpy, no jax, nothing of the program):
``voice.onnx.json`` is the Piper voice JSON of the configuration, and
``voice.npz`` holds the VITS parameters under the flat ``a/b/0/w`` keys of
the server's native format.  The same function feeds the reference, so the
program and the reference read the same numbers and neither made them.

Scales are chosen so that every stage carries signal at the published
widths (a dead stage would hide a fault in it):

- convolutions are variance preserving (uniform, bound sqrt(3 / fan_in)),
  biases zero;
- the duration predictor's last affine sets the mean duration
  (``frames_per_id`` is the calibration constant: at 3.05 every id's
  noise-free duration lies inside (2, 3), so it ceils to 3 frames, and at
  the published ``noise_w`` of 0.8 the mean is 3.5 frames per phoneme id,
  12.3 IPA characters a second at hop 256 and 22.05 kHz with Piper's
  interleaved pad ids; measured on the CPU, PERF.md section 4), and its
  flow projections are non-zero so that durations depend on the text and,
  with noise, on the draw;
- the coupling flows' ``post`` projections are non-zero, so the flow moves
  the latent;
- the decoder's last convolution is scaled so that the waveform fills the
  tanh's linear range.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

#: defaults of the VITS graph that a voice JSON's "model" block may override
MODEL_DEFAULTS = dict(
    inter_channels=192, hidden_channels=192, filter_channels=768, n_heads=2,
    n_layers=6, kernel_size=3, attn_window=4,
    resblock_kernel_sizes=[3, 7, 11],
    resblock_dilation_sizes=[[1, 3, 5], [1, 3, 5], [1, 3, 5]],
    upsample_rates=[8, 8, 2, 2], upsample_initial_channel=512,
    upsample_kernel_sizes=[16, 16, 4, 4], gin_channels=512,
    dp_filter_channels=192, dp_kernel_size=3, dp_n_flows=4, dp_num_bins=10,
    dp_tail_bound=5.0, flow_n_layers=4, flow_wn_layers=4, flow_kernel_size=5)

#: scale of the duration predictor's flow projections (text dependence of
#: the durations) and the mean of log-duration; read off a CPU run of the
#: reference at the published widths (PERF.md, cells)
DP_PROJ_STD = 0.03
FLOW_POST_GAIN = 0.5
CONV_POST_GAIN = 0.3


def model_dims(voice_json: dict) -> dict:
    dims = dict(MODEL_DEFAULTS)
    dims.update(voice_json.get("model", {}))
    return dims


def n_vocab(voice_json: dict) -> int:
    ids = [i for v in voice_json["phoneme_id_map"].values() for i in v]
    return max(int(voice_json.get("num_symbols", 256)), 1 + max(ids))


class _Init:
    def __init__(self, seed: int):
        self.flat: dict = {}
        self.rng = np.random.Generator(np.random.PCG64(int(seed)))

    def conv(self, key: str, k: int, c_in: int, c_out: int,
             gain: float = 1.0) -> None:
        bound = gain * math.sqrt(3.0 / (k * c_in))
        self.flat[key + "/w"] = self.rng.uniform(
            -bound, bound, (k, c_in, c_out)).astype(np.float32)
        self.flat[key + "/b"] = np.zeros((c_out,), np.float32)

    def normal(self, key: str, shape, std: float) -> None:
        self.flat[key] = (self.rng.standard_normal(shape) * std).astype(
            np.float32)

    def layer_norm(self, key: str, c: int) -> None:
        self.flat[key + "/gamma"] = np.ones((c,), np.float32)
        self.flat[key + "/beta"] = np.zeros((c,), np.float32)

    def dds(self, key: str, c: int, k: int) -> None:
        for i in range(3):
            p = f"{key}/layers/{i}"
            self.normal(p + "/dw/w", (k, 1, c), 1.0 / math.sqrt(k))
            self.flat[p + "/dw/b"] = np.zeros((c,), np.float32)
            self.conv(p + "/pw", 1, c, c)
            self.layer_norm(p + "/ln1", c)
            self.layer_norm(p + "/ln2", c)


def build_params(voice_json: dict, frames_per_id: float = 3.3,
                 seed: int = 0) -> dict:
    """Flat ``{key: float32 array}`` of every VITS parameter.

    A voice is one fixed artefact, as a published checkpoint is: every
    weight is drawn from the configuration's ``weights.seed``, the same for
    every run.  A run's ``--seed`` draws the traffic (words, order,
    speaker, which requests are compared) and not the weights, so that
    neither the work a cell does nor the size of the program's rounding
    error against the reference changes from run to run (PERF.md, cells)."""
    d = model_dims(voice_json)
    speakers = int(voice_json.get("num_speakers", 1))
    gin = d["gin_channels"] if speakers > 1 else 0
    h, inter, filt = (d["hidden_channels"], d["inter_channels"],
                      d["filter_channels"])
    head = h // d["n_heads"]
    win = d["attn_window"]
    it = _Init(seed)

    it.normal("enc_p/emb", (n_vocab(voice_json), h), h ** -0.5)
    for i in range(d["n_layers"]):
        p = f"enc_p/encoder/layers/{i}"
        for name in "qkvo":
            it.conv(f"{p}/attn/{name}", 1, h, h)
        it.normal(f"{p}/attn/emb_rel_k", (1, 2 * win + 1, head), head ** -0.5)
        it.normal(f"{p}/attn/emb_rel_v", (1, 2 * win + 1, head), head ** -0.5)
        it.layer_norm(f"{p}/ln1", h)
        it.conv(f"{p}/ffn/c1", d["kernel_size"], h, filt, gain=1.4)
        it.conv(f"{p}/ffn/c2", d["kernel_size"], filt, h)
        it.layer_norm(f"{p}/ln2", h)
    it.conv("enc_p/proj", 1, h, 2 * inter, gain=0.5)

    dpf, dpk = d["dp_filter_channels"], d["dp_kernel_size"]
    it.conv("dp/pre", 1, h, dpf)
    it.dds("dp/convs", dpf, dpk)
    it.conv("dp/proj", 1, dpf, dpf)
    # log-duration = (flow output - m) * exp(-logs): the mean of ceil(w)
    # sits about half a frame above exp(mean log w)
    it.flat["dp/affine/m"] = np.full(
        (2,), -math.log(max(frames_per_id - 0.5, 0.5)), np.float32)
    it.flat["dp/affine/logs"] = np.zeros((2,), np.float32)
    n_out = 3 * d["dp_num_bins"] - 1
    for i in range(d["dp_n_flows"]):
        p = f"dp/flows/{i}"
        it.conv(p + "/pre", 1, 1, dpf)
        it.dds(p + "/convs", dpf, dpk)
        it.normal(p + "/proj/w", (1, dpf, n_out), DP_PROJ_STD)
        it.flat[p + "/proj/b"] = np.zeros((n_out,), np.float32)
    if gin:
        # drawn last, so that a one-speaker and a many-speaker voice share
        # every other weight of the duration model; a weak hold of the
        # speaker on the durations: a speaker is drawn from the seed, and
        # may not move the cell's frame buckets
        it.conv("dp/cond", 1, gin, dpf, gain=0.02)

    half = inter // 2
    for i in range(d["flow_n_layers"]):
        p = f"flow/layers/{i}"
        it.conv(p + "/pre", 1, half, h)
        n_wn = d["flow_wn_layers"]
        for j in range(n_wn):
            it.conv(f"{p}/wn/in/{j}", d["flow_kernel_size"], h, 2 * h,
                    gain=1.4)
            it.conv(f"{p}/wn/res_skip/{j}", 1, h,
                    2 * h if j < n_wn - 1 else h)
        if gin:
            it.conv(f"{p}/wn/cond", 1, gin, 2 * h * n_wn)
        it.conv(p + "/post", 1, h, half, gain=FLOW_POST_GAIN)

    ch0 = d["upsample_initial_channel"]
    it.conv("dec/conv_pre", 7, inter, ch0, gain=1.4)
    if gin:
        it.conv("dec/cond", 1, gin, ch0)
    n_k = len(d["resblock_kernel_sizes"])
    for i, (r, k) in enumerate(zip(d["upsample_rates"],
                                   d["upsample_kernel_sizes"])):
        c_in, c_out = ch0 // 2 ** i, ch0 // 2 ** (i + 1)
        # a transposed conv of stride r gives each output k / r taps
        it.conv(f"dec/ups/{i}", k, c_in, c_out, gain=1.4 * math.sqrt(r))
        for j, (kr, dils) in enumerate(zip(d["resblock_kernel_sizes"],
                                           d["resblock_dilation_sizes"])):
            p = f"dec/resblocks/{i * n_k + j}"
            for di in range(len(dils)):
                it.conv(f"{p}/convs1/{di}", kr, c_out, c_out, gain=1.4)
                it.conv(f"{p}/convs2/{di}", kr, c_out, c_out, gain=0.7)
    c_last = ch0 // 2 ** len(d["upsample_rates"])
    it.conv("dec/conv_post", 7, c_last, 1, gain=CONV_POST_GAIN)
    if speakers > 1:
        it.normal("emb_g", (speakers, d["gin_channels"]), 1.0)
    return it.flat


def unflatten(flat: dict):
    """Nested dicts and lists from the flat keys (digits are list indices)."""
    root: dict = {}
    for key, value in flat.items():
        node = root
        parts = key.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value

    def listify(node):
        if not isinstance(node, dict):
            return node
        out = {k: listify(v) for k, v in node.items()}
        if out and all(k.isdigit() for k in out):
            return [out[str(i)] for i in range(len(out))]
        return out

    return listify(root)


def write_voice(out_dir, config: dict) -> Path:
    """``voice.onnx.json`` + ``voice.npz`` under ``out_dir``; returns the
    config path the server is started with."""
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    config_path = out / "voice.onnx.json"
    config_path.write_text(json.dumps(config["voice"]))
    flat = build_params(config["voice"], **config["weights"])
    with open(out / "voice.npz", "wb") as f:
        np.savez(f, **flat)
    return config_path


def describe(config: dict) -> dict:
    """What the harness has to know of the voice: the generator's hop, how
    many speakers a run may draw from, the graph's sizes for the cost
    functions, and that the stock path budgets a dispatch's frames by an
    estimator (``shapes.replay_estimator`` is a model of it)."""
    dims = model_dims(config["voice"])
    return {"samples_per_frame": math.prod(dims["upsample_rates"]),
            "num_speakers": int(config["voice"].get("num_speakers", 1)),
            "dims": dims, "frame_budget_estimator": True}


def reference_params(config: dict):
    """The voice's parameters as the reference takes them: nested, numpy."""
    return unflatten(build_params(config["voice"], **config["weights"]))
