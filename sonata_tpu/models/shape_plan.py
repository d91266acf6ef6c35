"""Which ``(batch, text, frame)`` shapes a stock voice runs, and which it
warms: the one owner of the shape decision.

A :class:`~sonata_tpu.models.piper.PiperVoice` compiles one executable
per bucket triple, and three decisions pick the triples.  All three live
here, as plain functions of what they read, over the ladders of
:mod:`sonata_tpu.utils.buckets`:

- :class:`FrameEstimator`: the frame budget of a dispatch, from a running
  upper bound of frames per input id (the frame count is data-dependent;
  the program takes a static budget and reports what the rows needed);
- :func:`plan_dispatch_groups`: how the rows of one ``speak_batch`` split
  into device programs;
- :func:`lattice_shapes`, :func:`window_decoder_batches` and
  :func:`neighbor_frame_buckets`: what a boot warms before readiness
  (``serving/warmup.py`` drives ``lattice_shapes`` through the voice).

Nothing here imports the voice, the engines or the serving plane: a
caller hands over what it resolved (the policy's batch sizes, the batch
mode, the length scale).
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

from ..utils.buckets import (
    BATCH_BUCKETS,
    FRAME_BUCKETS,
    TEXT_BUCKETS,
    bucket_for,
    canonical_dispatch_batch,
)
from ..utils.dispatch_policy import COALESCING_DEFAULTS

#: frames per input id per unit length scale before any observation.
#: Optimistic: an underestimate costs one overflow retry on the first
#: batch, an overestimate inflates every transfer (the wav buffer scales
#: with the frame bucket)
FRAMES_PER_ID_PRIOR = 2.5
#: headroom of a budget over the estimate.  The estimate is itself a
#: decaying upper bound over observed ratios, so this stays small: 1.25
#: pushed typical batches a whole frame bucket up, and every row then
#: shipped a ~2x transfer window back to the host.  Underestimates are
#: caught and cost one (rare) retry
BUDGET_HEADROOM = 1.08
#: margin on the first observation, which replaces the prior: it guards
#: the pipelined groups dispatched right after this single sample (one
#: low draw must not set a bound that makes every in-flight group
#: overflow and rerun)
FIRST_OBSERVATION_MARGIN = 1.15
#: the bound shrinks by this factor a dispatch, and jumps up at once
DECAY = 0.995
#: floor of a row's length scale wherever it weighs an id count
MIN_LENGTH_SCALE = 0.05
#: the chunk-growth schedule caps at 1024 frames plus padding, so this
#: is the largest window bucket a stream's plan can produce
MAX_WINDOW_BUCKET = 1536


class FrameEstimator:
    """Adaptive frame budget of the single-dispatch path.

    ``weighted_ids`` is, throughout, the max over a dispatch's rows of
    ``len(ids) * length_scale``: the true per-row frame driver (a batch
    mixing a long 1x row with a short 3x row must not be budgeted as
    long x 3x)."""

    def __init__(self):
        self.frames_per_id = FRAMES_PER_ID_PRIOR
        self.observed = False  # first real observation landed?
        self._lock = threading.Lock()

    def budget(self, weighted_ids: float) -> tuple[int, float]:
        """``(frames budgeted, the frames per id it used)``."""
        with self._lock:
            fpi = self.frames_per_id
        return max(int(weighted_ids * fpi * BUDGET_HEADROOM), 1), fpi

    def bucket(self, weighted_ids: float) -> int:
        """The frame bucket :meth:`budget` rounds up to."""
        return bucket_for(self.budget(weighted_ids)[0], FRAME_BUCKETS)

    def observe(self, weighted_ids: float, frames: int) -> None:
        """Feed what a real dispatch's longest row needed."""
        ratio = frames / max(weighted_ids, 1.0)
        with self._lock:
            if not self.observed:
                # the first real observation replaces the cold-start
                # prior: decaying down from a too-high prior at 0.5% per
                # batch would overshoot the frame bucket (and its per-row
                # transfer window) for hundreds of batches
                self.frames_per_id = ratio * FIRST_OBSERVATION_MARGIN
            else:
                self.frames_per_id = max(self.frames_per_id * DECAY, ratio)
            self.observed = True


def plan_dispatch_groups(lengths: Sequence[int],
                         length_scales: Sequence[float], *,
                         min_batch: int, max_batch: int) -> list[list[int]]:
    """Partition row indices into device-dispatch groups.

    ``lengths[i]`` ids at ``length_scales[i]`` each.  Rows sort by
    estimated frame count, then split into contiguous groups whose sizes
    are exact batch buckets (zero dummy rows: a dummy row still copies a
    full frame-bucket window of samples back to the host).  Group sizes
    cap at half the batch (``min_batch`` at least, ``max_batch`` at most)
    so at least two dispatches pipeline compute against result transfer;
    sorted order keeps each group's frame bucket tight.
    """
    n = len(lengths)

    def est_frames(i) -> float:
        # relative frame driver per row; the shared frames-per-id factor
        # cancels in a sort, so it stays out of the key
        return lengths[i] * max(float(length_scales[i]), MIN_LENGTH_SCALE)

    def split_by_text_bucket(group: list[int]) -> list[list[int]]:
        """Split where a row's text bucket jumps past 2x the current
        subgroup head's (re-based per subgroup: a 16→64→512 tier mix
        splits twice): a frame-alike but text-length-wild mix (possible
        with per-row length_scale overrides) would otherwise pad every
        short row's text, and its frame-bucket transfer window, to the
        outlier's size.  Off-bucket subgroup sizes just pad a few dummy
        rows."""
        out: list[list[int]] = []
        for i in group:
            tb = bucket_for(lengths[i], TEXT_BUCKETS)
            if not out or tb > 2 * bucket_for(lengths[out[-1][0]],
                                              TEXT_BUCKETS):
                out.append([i])
            else:
                out[-1].append(i)
        return out

    order = sorted(range(n), key=est_frames)
    if n < 2 * min_batch:
        return split_by_text_bucket(order)
    # cap a group at half the batch (bucket-rounded down) so there are
    # always ≥2 dispatches to pipeline; never below min or above max
    half = max((n + 1) // 2, min_batch)
    cap = next(s for s in reversed(BATCH_BUCKETS) if s <= half)
    cap = min(cap, max_batch)
    # decompose n into bucket sizes ≤ cap, smallest group first so the
    # leftover (non-power-of-two) rows are the *short* ones
    sizes: list[int] = []
    rest = n
    while rest:
        take = min(cap, rest)
        sizes.append(next((s for s in reversed(BATCH_BUCKETS)
                           if s <= take), BATCH_BUCKETS[0]))
        rest -= sizes[-1]
    sizes.sort()
    # a leftover smaller than min rides inside the next group as extra
    # rows, but only while the merged group stays near its batch bucket:
    # a few padding dummies cost less than a tiny dispatch's fixed cost,
    # a few dozen cost more
    while len(sizes) > 1 and sizes[0] < min_batch:
        merged = sizes[0] + sizes[1]
        if (merged > max_batch
                or bucket_for(merged, BATCH_BUCKETS) - merged > min_batch):
            break
        small = sizes.pop(0)
        sizes[0] += small
    groups, pos = [], 0
    for s in sizes:
        groups.extend(split_by_text_bucket(order[pos:pos + s]))
        pos += s
    return groups


def neighbor_frame_buckets(f: int) -> set[int]:
    """The frame buckets next to ``f`` (``f`` itself left out): the frame
    estimate rides each request's random duration draw, so traffic lands
    one bucket over routinely.  A bucket beyond the table has no
    neighbour schedule."""
    if f not in FRAME_BUCKETS:
        return set()
    i = FRAME_BUCKETS.index(f)
    return {FRAME_BUCKETS[max(i - 1, 0)],
            FRAME_BUCKETS[min(i + 1, len(FRAME_BUCKETS) - 1)]} - {f}


def window_decoder_batches(batch_mode: str, max_batch: int) -> list[int]:
    """The batch sizes a window-decode engine pads to.  The iteration
    loop steps the graduated ladder (1, 2, 4, ..., max): that is where
    its padding-waste win comes from.  The dispatch-mode coalescers pad
    every multi-request group to ONE canonical size, so their executable
    set is exactly {1, max}."""
    if batch_mode == "iteration":
        return [b for b in BATCH_BUCKETS if b <= max_batch]
    return sorted({1, max_batch})


def lattice_shapes(mode: str, estimator: FrameEstimator,
                   length_scale: float, *, scheduler_max_batch: int,
                   stream_decode_max_batch: int,
                   batch_mode: Optional[str],
                   multi_speaker: bool) -> list[tuple]:
    """Enumerate the shapes a restart must warm (``PiperVoice.
    lattice_shapes``), smallest first so a budget expiry leaves the most
    common shapes warm.

    The full-pipeline triples real traffic can hit:

    - text axis: every :data:`TEXT_BUCKETS` entry (any sentence lands in
      one of them);
    - frame axis: the RANGE of buckets ``estimator`` can pick across the
      text bucket's id-length span (a sentence in bucket 128 may hold
      anywhere from 97 to 128 ids, and the estimate is linear in that
      length; callers should run one *real* calibration utterance first
      so this enumerates with an observed frames-per-id, not the prior),
      plus the next bucket UP in every mode (the estimator jumps up
      *immediately* on a higher observation, so the first post-warm
      sentence with a long duration draw lands there), plus the bucket
      below the range in ``full`` mode (slow downward decay under
      sustained traffic);
    - batch axis: 1 (sequential / per-request dispatch), plus, in
      ``full`` mode, the canonical coalesced batch the scheduler pads
      multi-request groups to (``scheduler_max_batch``; 1, a per-request
      policy, adds nothing).

    ``minimal`` is the batch-1, estimated-bucket-only subset, strictly
    contained in ``full``.  ``off`` returns [] (the caller keeps the
    legacy one-utterance warmup).

    Then, when ``batch_mode`` is ``"iteration"``, the window-decoder
    shapes of the persistent decode loop, tagged ``("wdec", width,
    batch, has_sid)``: every rung of :func:`window_decoder_batches` x
    every reachable window width must be warm, or the first
    mid-occupancy iteration pays a cold compile the cold-compile
    containment would rightly flag.  ``minimal`` keeps batch 1 only
    (single-resident-stream serving); iteration-mode deployments should
    warm ``full``.  ``stream_decode_max_batch`` of 1 (iteration forced
    onto a per-request policy) still wants a real batch axis and takes
    the coalescing default.  ``batch_mode`` of ``None`` says the caller
    could not resolve it: no such shapes (that must not block boot).
    """
    if mode == "off":
        return []
    batches = {1}
    if mode == "full":
        canonical = canonical_dispatch_batch(scheduler_max_batch)
        if canonical > 1:
            batches.add(canonical)
    ls = max(length_scale, MIN_LENGTH_SCALE)
    shapes: list[tuple] = []
    n_fb = len(FRAME_BUCKETS)
    for ti, t in enumerate(TEXT_BUCKETS):
        # shortest and longest id counts that pad to this bucket
        lo_ids = TEXT_BUCKETS[ti - 1] + 1 if ti > 0 else 1
        f_lo = estimator.bucket(lo_ids * ls)
        f_hi = estimator.bucket(t * ls)
        frames = {f_lo, f_hi}
        if f_lo in FRAME_BUCKETS:
            i_lo = FRAME_BUCKETS.index(f_lo)
            # an f_hi past the table (bucket_for returns top-bucket
            # multiples there) still needs the reachable IN-TABLE run
            # warmed: clamping to the top keeps the range covered
            # instead of silently skipping it
            i_hi = (FRAME_BUCKETS.index(f_hi)
                    if f_hi in FRAME_BUCKETS else n_fb - 1)
            if mode == "full":
                i_lo = max(i_lo - 1, 0)
            frames.update(FRAME_BUCKETS[i]
                          for i in range(i_lo, min(i_hi + 2, n_fb)))
        for b in sorted(batches):
            for f in sorted(frames):
                shapes.append((b, t, f))
    shapes.sort(key=lambda s: (s[1], s[0], s[2]))
    if batch_mode == "iteration":
        max_b = stream_decode_max_batch
        if max_b <= 1:
            max_b = COALESCING_DEFAULTS["stream_decode_max_batch"]
        ladder = ([1] if mode == "minimal"
                  else window_decoder_batches("iteration", max_b))
        shapes.extend(("wdec", w, b, bool(multi_speaker))
                      for w in FRAME_BUCKETS if w <= MAX_WINDOW_BUCKET
                      for b in ladder)
    return shapes
