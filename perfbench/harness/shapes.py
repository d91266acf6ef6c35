"""What the per-layer readers share: the full-pipeline programs of the
traced interval with their padded shapes, read off the device trace."""

from __future__ import annotations

import math


def hop(run) -> int:
    return math.prod(run["dims"]["upsample_rates"])


def programs(run) -> list:
    """``{"b", "f", "seconds"}`` of each executed program whose largest
    tensor is a waveform of at least 64 frames: a full-pipeline dispatch.
    The generator's last stage holds ``[b, f * hop, channels]``."""
    trace = run.get("trace") or {}
    h = hop(run)
    out = []
    for m in trace.get("modules", []):
        frames, rest = divmod(m.get("largest_dim", 0), h)
        if frames >= 64 and not rest and m.get("batch"):
            out.append({"b": m["batch"], "f": frames,
                        "seconds": m["dur_ns"] / 1e9})
    return out


def traced_interval(run) -> tuple:
    """Wall-clock start and end of the traced interval."""
    trace, profile = run["trace"], run["profile"]
    a = trace.get("wall_t0", profile["wall_start"])
    return a, a + trace["window_s"]


def text_bucket(run) -> int:
    """The padded text length of the cell's longest sentence (ids = 2 *
    characters + 2, rounded up to a multiple of 64); the text stages are
    under 1 % of a dispatch's operations, so one value serves all."""
    longest = max(max(p) for p in run["traffic"]["paragraphs"])
    return (2 * longest + 2 + 63) // 64 * 64


#: the stock path's frame buckets and frame-budget estimator, copied from
#: ``sonata_tpu/utils/buckets.py`` and ``PiperVoice._estimate_frame_bucket``
#: / ``_observe_frames`` as they stood in PR 24
FRAME_BUCKETS = (64, 128, 256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096)


def replay_estimator(records: list, paragraphs: list, hop: int, t0: float,
                     t1: float) -> dict:
    """The frame bucket of each request that ended in ``[t0, t1]``, worked
    out from outside: the stock server's dispatch spans carry no bucket,
    but the generator knows every request's longest row (ids) and the
    frames that came back, which is all the estimator sees.  Each request
    is budgeted when it starts and observed when it ends.  A model of the
    server's state, not a reading of it: an untraced run's stand-in for the
    histogram a traced run reads off the device."""
    events = sorted([(r["t_start"], 0, i) for i, r in enumerate(records)]
                    + [(r["t_end"], 1, i) for i, r in enumerate(records)])
    fpi, budget = None, {}
    buckets, retries, seen = {}, 0, []
    for t, kind, i in events:
        r = records[i]
        ids = 2 * max(paragraphs[r["paragraph"]]) + 2
        if kind == 0:
            est = int(ids * (fpi or 2.5) * 1.08)
            budget[i] = next((b for b in FRAME_BUCKETS if est <= b),
                             FRAME_BUCKETS[-1])
            continue
        if not r["ok"]:
            continue
        frames = max(r["samples"]) // hop
        fpi = (frames / ids * 1.15 if fpi is None
               else max(fpi * 0.995, frames / ids))
        if t0 <= t <= t1:
            f = budget[i]
            if frames > f:
                retries += 1
                f = next(b for b in FRAME_BUCKETS if frames <= b)
            buckets[f] = buckets.get(f, 0) + 1
            seen.append(fpi)
    return {"frame_buckets": {str(k): v for k, v in sorted(buckets.items())},
            "overflow_retries": retries,
            "frames_per_id_estimate": ([min(seen), sum(seen) / len(seen),
                                        max(seen)] if seen else None)}
