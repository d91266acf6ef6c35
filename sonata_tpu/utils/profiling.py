"""Observability: RTF counters and profiler trace capture.

The reference's entire tracing story is a wall-clock around each ORT run
surfaced as ``real_time_factor`` (SURVEY §5).  We keep that (every
``Audio`` carries ``inference_ms``) and add the TPU-native pieces the
survey calls for: aggregate RTF counters and ``jax.profiler`` trace
capture for Tensorboard/XProf.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass
from typing import Optional


@dataclass
class RtfStats:
    utterances: int = 0
    audio_ms: float = 0.0
    inference_ms: float = 0.0

    @property
    def rtf(self) -> float:
        return self.inference_ms / self.audio_ms if self.audio_ms else 0.0

    @property
    def audio_seconds_per_second(self) -> float:
        return 1.0 / self.rtf if self.rtf else 0.0


class RtfCounter:
    """Thread-safe aggregate RTF accounting (e.g. one per gRPC server)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats = RtfStats()

    def record(self, audio) -> None:
        """Record one synthesized :class:`~sonata_tpu.audio.Audio`."""
        with self._lock:
            self._stats.utterances += 1
            self._stats.audio_ms += audio.duration_ms()
            self._stats.inference_ms += audio.inference_ms

    def snapshot(self) -> RtfStats:
        with self._lock:
            return RtfStats(self._stats.utterances, self._stats.audio_ms,
                            self._stats.inference_ms)

    def reset(self) -> None:
        with self._lock:
            self._stats = RtfStats()


#: Default latency buckets (seconds): 5 ms .. 30 s, roughly 2.5x apart.
#: Spans a TTFB on a warm accelerator (~tens of ms) through a cold-compile
#: first request (tens of seconds); everything beyond lands in +Inf.
DEFAULT_LATENCY_BUCKETS_S = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                             1.0, 2.5, 5.0, 10.0, 30.0)

#: Queue-wait buckets (seconds): a request's time in the batch scheduler
#: queue is normally sub-millisecond (the gather window) but stretches to
#: seconds when the voice is backed up — the low end needs resolution the
#: latency buckets don't have.
QUEUE_WAIT_BUCKETS_S = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                        0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


@dataclass
class HistogramSnapshot:
    """Point-in-time copy of a :class:`Histogram` (cumulative counts)."""

    buckets: tuple  # upper bounds, seconds (excluding +Inf)
    counts: tuple   # cumulative count per bound, then the +Inf total last
    total: int
    sum: float


class Histogram:
    """Thread-safe bounded-bucket histogram (Prometheus-style cumulative).

    Fixed bucket bounds chosen at construction keep memory constant no
    matter how many observations arrive — the property that makes it safe
    as an always-on serving metric (vs. recording raw samples).
    """

    def __init__(self, buckets=None):
        bounds = tuple(sorted(buckets or DEFAULT_LATENCY_BUCKETS_S))
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._bounds = bounds
        self._counts = [0] * (len(bounds) + 1)  # last = +Inf overflow
        self._sum = 0.0
        self._total = 0
        self._lock = threading.Lock()

    @property
    def bounds(self) -> tuple:
        return self._bounds

    def observe(self, value: float) -> None:
        # linear scan: bucket lists are ~a dozen entries, and the scan is
        # cheaper than bisect's function-call overhead at this size
        idx = len(self._bounds)
        for i, b in enumerate(self._bounds):
            if value <= b:
                idx = i
                break
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._total += 1

    def snapshot(self) -> HistogramSnapshot:
        with self._lock:
            counts = list(self._counts)
            total, s = self._total, self._sum
        # cumulative counts, Prometheus exposition semantics
        cum = []
        running = 0
        for c in counts:
            running += c
            cum.append(running)
        return HistogramSnapshot(buckets=self._bounds, counts=tuple(cum),
                                 total=total, sum=s)


#: the jax profiler cannot nest captures; serialize /debug/profile hits
_PROFILE_LOCK = threading.Lock()

#: true while :func:`capture_profile` holds a capture: what
#: :func:`annotation` reads, so that outside a capture a mirrored span
#: costs this one flag read
_capturing = False

NO_ANNOTATION = contextlib.nullcontext()


def annotation(name: str, **ids):
    """A host span inside the profiler's own trace, while
    :func:`capture_profile` holds a capture; a shared no-op context
    otherwise.  ``ids`` (strings or numbers, e.g. ``dispatch_id``) are
    stored with the event, so a reader joins host spans to the program's
    own records by id and to device operations by time: one file, one
    clock."""
    if not _capturing:
        return NO_ANNOTATION
    import jax

    return jax.profiler.TraceAnnotation(name, **ids)


def clock_anchor() -> dict:
    """The wall clock and the monotonic clock (the one the program's
    spans use) read together."""
    return {"wall": time.time(), "monotonic": time.monotonic()}


def capture_profile(seconds: float, log_dir: Optional[str] = None) -> dict:
    """Capture a ``jax.profiler`` device trace for ``seconds``.  Returns
    ``{"log_dir", "anchors"}``: the directory to view with
    Tensorboard/XProf or Perfetto, and the host's clocks read immediately
    before ``start_trace`` was called (``start_called``), after it
    returned (``start_returned``), and around ``stop_trace``
    (``stop_called``, ``stop_returned``).  The trace's own clock starts
    between the first two (on a v5e a quarter of a millisecond after the
    first, PERF.md), so a reader places wall-clock times on it without
    fitting anything.

    What the metrics plane's ``/debug/profile?seconds=`` endpoint runs:
    the tracing layer answers *where a request's wall time went*; this
    answers *what the device was doing meanwhile*, and while it runs the
    program's spans are mirrored into the same file (:func:`annotation`).
    Raises ``RuntimeError`` when a capture is already running (the
    profiler cannot nest).
    """
    import tempfile

    import jax

    global _capturing
    seconds = min(max(float(seconds), 0.1), 60.0)
    if log_dir is None:
        log_dir = tempfile.mkdtemp(prefix="sonata_profile_")
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise RuntimeError("a profiler capture is already running")
    anchors = {}
    try:
        anchors["start_called"] = clock_anchor()
        jax.profiler.start_trace(log_dir)
        try:
            anchors["start_returned"] = clock_anchor()
            _capturing = True
            time.sleep(seconds)
        finally:
            _capturing = False
            anchors["stop_called"] = clock_anchor()
            jax.profiler.stop_trace()
            anchors["stop_returned"] = clock_anchor()
    finally:
        _PROFILE_LOCK.release()
    return {"log_dir": log_dir, "anchors": anchors}
