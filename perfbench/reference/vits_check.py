"""The comparison that decides ``correct`` for a VITS voice behind the stock
RPCs: ``compare(job, config)``, called by ``perfbench.reference.check`` (which
holds the protocol) in the check child, once the window has closed and the
server has gone.  It runs the plain reference the configuration names under
``reference`` over the sampled requests and their served audio, on the
parameters the configuration's ``writer`` gives.

What is compared, per sampled sentence (a row):

1. Text -> phoneme ids by the benchmark's own word list.
2. The served audio has a whole number of frames, ``F``.
3. The reference gives real-valued durations ``w`` per id.  The served
   integer durations are not on the wire, so they are recovered: the
   reference's graph evaluated at the backend's *default* matmul precision
   (what the program runs at) gives ``ceil(w)`` that agrees with the
   program up to near-ties; where the total differs from ``F``, or the
   audio does not match, the ids nearest to an integer are moved one frame
   (at most ``AMBIGUOUS`` ids are considered).  A row for which no such
   assignment gives ``F`` frames is unaligned and counts as an error of 1.
4. The reference at ``highest`` precision, run over the recovered
   durations, gives the waveform; it is peak-normalised as the server's
   int16 conversion does, and ``audio_err`` is the relative r.m.s. error
   of the served samples against it.  A row's error is the least over the
   candidate assignments: "is there an assignment within the reference's
   near-ties that explains what was served".
5. The configuration's arithmetic is float32 at the backend's default
   matmul precision, which on a TPU rounds every product's operands to
   bfloat16: the program is that far from the reference by right, and how
   far depends on the text.  So the same rows go through the reference at
   the default precision too, and ``audio_err_ratio`` is a row's
   ``audio_err`` over the reference's own single-pass error on that row:
   how much rounding the served audio carries, in units of what one plain
   float32 implementation carries on this chip.  The median over the rows
   is compared.  Where default and
   ``highest`` agree (the CPU) there is no such unit and the ratio is left
   out.
6. ``dur_gap`` is the widest distance by which a recovered duration lies
   outside the interval ``(d - 1, d]`` of the reference's ``w`` at
   ``highest``: the analogue of a served token's logit gap (reported, not
   compared).

``PERFBENCH_CONTROL=reference_bfloat16`` (or ``reference_fp8``) puts the
reference, computed in that storage type, in the program's place: the
control that has to come out as not correct.  ``PERFBENCH_ALSO_CONTROLS``
(a comma-separated list of the same names) leaves the run as it is and
adds each control's numbers under ``info.controls``: how the limits'
upper ends are read without a second boot of the server.
"""

from __future__ import annotations

import itertools
import os
import random
import time
from pathlib import Path

import numpy as np

from perfbench.harness import parts, textgen

#: ids per row that may be moved by one frame when recovering durations
AMBIGUOUS = 6
#: candidate assignments tried per row beyond the first
MAX_ALTERNATES = 16
ROWS_PER_BLOCK = 8


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def candidates(w_al: np.ndarray, n_ids: int, frames: int) -> list:
    """Integer duration vectors consistent with ``w_al`` up to near-ties
    whose total is ``frames``, the likeliest first."""
    w = w_al[:n_ids]
    base = np.ceil(w).astype(np.int64)
    up = base - w            # small: the program may have ceiled to base+1
    down = w - (base - 1)    # small: the program may have ceiled to base-1
    margin = np.minimum(up, down)
    pick = np.argsort(margin)[:AMBIGUOUS]
    moves = [(int(i), 1 if up[i] <= down[i] else -1) for i in pick]
    out = []
    for r in range(len(moves) + 1):
        for combo in itertools.combinations(range(len(moves)), r):
            d = base.copy()
            cost = 0.0
            for c in combo:
                i, step = moves[c]
                d[i] += step
                cost += float(margin[i])
            if d.min() >= 0 and int(d.sum()) == frames:
                out.append((cost, d))
    out.sort(key=lambda t: t[0])
    return [d for _, d in out[:1 + MAX_ALTERNATES]]


def peak_normalised(wav: np.ndarray, n: int) -> np.ndarray:
    cut = wav[:n].astype(np.float64)
    return cut / max(float(np.max(np.abs(cut))), 0.01)


def rel_rms(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.sqrt(np.sum((got - want) ** 2)
                         / max(np.sum(want ** 2), 1e-12)))


def rel_rms_error(served_pcm: np.ndarray, wav: np.ndarray) -> float:
    """Served int16 samples against the reference waveform, normalised to
    its peak as the server's int16 conversion normalises."""
    served = served_pcm.astype(np.float64) / 32767.0
    return rel_rms(served, peak_normalised(wav, len(served)))


#: under this the reference's default and ``highest`` precisions are the
#: same arithmetic, and there is no single-pass error to measure against
NO_SINGLE_PASS = 1e-5


#: ``PERFBENCH_CONTROL=<name>``: the storage type of the control
CONTROL_DTYPES = {"reference_bfloat16": "bfloat16",
                  "reference_fp8": "float8_e4m3fn"}


def serve_control(ref, rows: list, params, dims: dict, speaker,
                  dtype: str) -> None:
    """The control: the reference put in the program's place and computed
    in a precision below the configuration's (weights and every
    convolution's input and output rounded to ``dtype``).  Each row's
    served audio is replaced by what that gives."""
    import jax
    import jax.numpy as jnp

    def q(a):
        return a.astype(getattr(jnp, dtype)).astype(jnp.float32)

    plain, plain_t = ref.conv, ref.conv_transpose
    ref.conv = lambda x, p, **kw: q(plain(
        q(x), {"w": q(p["w"]), "b": q(p["b"])}, **kw))
    ref.conv_transpose = lambda x, p, stride: q(plain_t(
        q(x), {"w": q(p["w"]), "b": q(p["b"])}, stride))
    try:
        for s in range(0, len(rows), ROWS_PER_BLOCK):
            block = rows[s:s + ROWS_PER_BLOCK]
            t_pad = _round_up(max(len(r["ids"]) for r in block), 64)
            ids = np.zeros((len(block), t_pad), np.int32)
            for k, r in enumerate(block):
                ids[k, :len(r["ids"])] = r["ids"]
            lens = np.array([len(r["ids"]) for r in block], np.int32)
            sid = None if speaker is None else jnp.full(
                (len(block),), speaker, jnp.int32)
            m_p, logs_p, w = jax.jit(
                lambda p, i, n: ref.encode(p, dims, i, n, sid))(
                params, ids, lens)
            dur = jnp.ceil(w).astype(jnp.int32)
            frames = _round_up(int(jnp.max(jnp.sum(dur, 1))), 256)
            wav, y_len = jax.jit(
                lambda p, m, lg, d: ref.synthesize(p, dims, m, lg, d,
                                                   frames, sid))(
                params, m_p, logs_p, dur)
            wav, hop = np.asarray(wav), ref.hop_length(dims)
            for k, r in enumerate(block):
                cut = wav[k, :int(y_len[k]) * hop]
                peak = max(float(np.max(np.abs(cut))), 0.01)
                r["pcm"] = np.clip(cut * (32767.0 / peak), -32768,
                                   32767).astype(np.int16)
    finally:
        ref.conv, ref.conv_transpose = plain, plain_t


def compare(job: dict, config: dict) -> dict:
    import jax
    import jax.numpy as jnp

    root = Path(job["root"])
    ref = parts.load(root, job["paths"], config, "reference")
    writer = parts.load(root, job["paths"], config, "writer")
    sampled = [req for req in job["sampled"] if req["ok"]]
    dims = writer.describe(config)["dims"]
    hop = ref.hop_length(dims)
    id_map = config["voice"]["phoneme_id_map"]
    lexicon = textgen.Lexicon(root / job["words"])
    params = jax.tree_util.tree_map(jnp.asarray,
                                    writer.reference_params(config))

    audio = np.load(job["sampled_audio"])
    rows = []
    for req in sampled:
        for i, sentence in enumerate(req["sentences"]):
            rows.append({"ids": textgen.text_to_ids(lexicon, sentence,
                                                    id_map),
                         "pcm": audio[f"{req['seq']}_{i}"]})
    # a sample of the sampled requests' rows, drawn from the seed, with
    # the longest in it
    wanted = int(job.get("rows") or len(rows))
    if len(rows) > wanted:
        longest = max(range(len(rows)), key=lambda k: len(rows[k]["pcm"]))
        rest = [k for k in range(len(rows)) if k != longest]
        random.Random(job["seed"]).shuffle(rest)
        rows = [rows[k] for k in sorted([longest] + rest[:wanted - 1])]
    speaker = job.get("speaker")
    control = os.environ.get("PERFBENCH_CONTROL")
    if control:
        serve_control(ref, rows, params, dims, speaker,
                      CONTROL_DTYPES[control])
    t_pad = _round_up(max(len(r["ids"]) for r in rows), 64)
    n = _round_up(len(rows), ROWS_PER_BLOCK)
    ids = np.zeros((n, t_pad), np.int32)
    lens = np.ones((n,), np.int32)
    for k, r in enumerate(rows):
        ids[k, :len(r["ids"])] = r["ids"]
        lens[k] = len(r["ids"])

    def sid_of(b):
        return None if speaker is None else jnp.full((b,), speaker,
                                                     jnp.int32)

    @jax.jit
    def encode_block(params, ids, lens, sid):
        return ref.encode(params, dims, ids, lens, sid)

    t0 = time.monotonic()
    w_al, m_p, logs_p, w_hi = [], [], [], []
    for s in range(0, n, ROWS_PER_BLOCK):
        blk = slice(s, s + ROWS_PER_BLOCK)
        # the aligner: the backend's default precision, as the program
        sid = sid_of(ROWS_PER_BLOCK)
        _, _, w = encode_block(params, ids[blk], lens[blk], sid)
        w_al.append(np.asarray(w))
        with jax.default_matmul_precision("highest"):
            m, lg, w = encode_block(params, ids[blk], lens[blk], sid)
        m_p.append(m), logs_p.append(lg), w_hi.append(np.asarray(w))
    w_al, w_hi = np.concatenate(w_al), np.concatenate(w_hi)
    m_p, logs_p = jnp.concatenate(m_p), jnp.concatenate(logs_p)
    f_pad = _round_up(max([len(r["pcm"]) // hop for r in rows] + [1]), 256)

    @jax.jit
    def synth(params, m_p, logs_p, durations, sid):
        wav, _ = ref.synthesize(params, dims, m_p, logs_p, durations, f_pad,
                                sid)
        return wav

    def errors(rows: list, pairs: list, single_pass: bool) -> list:
        """``(audio_err, the reference's own single-pass error or None)``
        of each (row index, durations) pair."""
        out_err = []
        for s in range(0, len(pairs), ROWS_PER_BLOCK):
            block = pairs[s:s + ROWS_PER_BLOCK]
            pad = block + [block[-1]] * (ROWS_PER_BLOCK - len(block))
            idx = np.array([k for k, _ in pad])
            dur = np.zeros((ROWS_PER_BLOCK, t_pad), np.int32)
            for j, (_, d) in enumerate(pad):
                dur[j, :len(d)] = d
            args = (params, m_p[idx], logs_p[idx], dur,
                    sid_of(ROWS_PER_BLOCK))
            with jax.default_matmul_precision("highest"):
                wav = np.asarray(synth(*args))
            wav_1 = np.asarray(synth(*args)) if single_pass else None
            for j, (k, _) in enumerate(block):
                pcm = rows[k]["pcm"]
                own = None if wav_1 is None else rel_rms(
                    peak_normalised(wav_1[j], len(pcm)),
                    peak_normalised(wav[j], len(pcm)))
                out_err.append((rel_rms_error(pcm, wav[j]), own))
        return out_err

    limit = float(job["limits"]["audio_err_max"])

    def measure(rows: list) -> tuple:
        """The numbers compared, and what else is worth printing, of rows
        whose ``pcm`` is what was served."""
        unaligned = 0
        for k, r in enumerate(rows):
            r["frames"], rem = divmod(len(r["pcm"]), hop)
            fits = not rem and r["frames"] <= f_pad
            r["cands"] = candidates(w_al[k], len(r["ids"]),
                                    r["frames"]) if fits else []
            if not r["cands"]:
                unaligned += 1
        first = [(k, r["cands"][0]) for k, r in enumerate(rows)
                 if r["cands"]]
        for (k, d), (e, own) in zip(first, errors(rows, first, True)):
            rows[k]["err"], rows[k]["own"], rows[k]["dur"] = e, own, d
        retry = [(k, d) for k, r in enumerate(rows) if r["cands"]
                 and r["err"] > limit for d in r["cands"][1:]]
        improved = set()
        for (k, d), (e, _) in zip(retry, errors(rows, retry, False)
                                  if retry else []):
            if e < rows[k]["err"]:
                rows[k]["err"], rows[k]["dur"] = e, d
                improved.add(k)
        gaps, moved = [], 0
        for k, r in enumerate(rows):
            if not r["cands"]:
                r["err"], r["own"] = 1.0, None
                continue
            w = w_hi[k][:len(r["ids"])]
            d = r["dur"].astype(np.float64)
            gaps.append(float(np.max(np.maximum(0.0, np.maximum(
                w - d, (d - 1.0) - w)))))
            moved += not np.array_equal(
                r["dur"],
                np.ceil(w_al[k][:len(r["ids"])]).astype(np.int64))
        errs = np.array([r["err"] for r in rows])
        ratios = [r["err"] / r["own"] for r in rows
                  if r["own"] is not None and r["own"] > NO_SINGLE_PASS]
        numbers = {
            "audio_err_max": float(errs.max()),
            # the median row: steady to half a percent from seed to seed,
            # where the worst row swings by four
            "audio_err_ratio_median": (float(np.median(ratios))
                                       if len(ratios) == len(rows)
                                       else None),
            "rows_unaligned": unaligned,
        }
        info = {
            "audio_err_mean": float(errs.mean()),
            "audio_err_median": float(np.median(errs)),
            "audio_err_ratio_max": max(ratios) if ratios else None,
            "audio_err_ratio_min": min(ratios) if ratios else None,
            "single_pass_err_median": (float(np.median(
                [r["own"] for r in rows if r["own"] is not None]))
                if ratios else None),
            "dur_gap_max": max(gaps) if gaps else 1.0,
            "rows_moved_from_default_precision": int(moved),
            "rows_realigned_on_audio": len(improved),
        }
        return numbers, info

    out = {}
    out["numbers"], out["info"] = measure(rows)
    out["info"].update({
        "rows": len(rows), "requests": len(sampled),
        "frames_compared": int(sum(r["frames"] for r in rows)),
        "longest_row_frames": int(max(r["frames"] for r in rows)),
        "frames_per_id": float(sum(r["frames"] for r in rows)
                               / sum(len(r["ids"]) for r in rows)),
        "padded_shape": [ROWS_PER_BLOCK, t_pad, f_pad],
    })
    also = [c for c in os.environ.get("PERFBENCH_ALSO_CONTROLS",
                                      "").split(",") if c]
    for name in also:
        copies = [{"ids": r["ids"], "pcm": r["pcm"]} for r in rows]
        serve_control(ref, copies, params, dims, speaker,
                      CONTROL_DTYPES[name])
        numbers, info = measure(copies)
        out["info"].setdefault("controls", {})[name] = dict(
            numbers, audio_err_ratio_max=info["audio_err_ratio_max"],
            audio_err_ratio_min=info["audio_err_ratio_min"])
    out["info"]["reference_s"] = time.monotonic() - t0
    return out
