"""Request-scoped tracing: span pipeline, dispatch attribution, capture.

The serving plane so far exposes only *aggregate* signals — RTF counters,
TTFB/latency histograms, shed/expired counters.  When one stream's TTFB
blows past p99 those cannot say whether the time went to queue wait,
coalescer gather, a cold bucket compile, a breaker-driven resubmission,
or the decode itself.  This module is the Dapper-style answer (Sigelman
et al., 2010): every request carries a ``request_id`` (accepted from gRPC
metadata ``x-request-id`` or generated) and grows a span tree across the
pipeline — text-normalize → phonemize → encode-ids → admission →
queue-wait → dispatch → decode → postprocess → stream-emit.

Design constraints, in order:

- **Lock-cheap and always-on-capable.**  A span is a monotonic-clock pair
  plus a dict; recording appends to a per-trace list under a per-trace
  lock.  Every hook is a no-op (one contextvar read) when no trace is
  active, so library code can be instrumented unconditionally.
- **Cross-thread by construction.**  The pipeline hops threads (gRPC
  handler → scheduler worker → coalescer/finisher), so context does not
  travel implicitly: the scheduler captures ``current()`` at submit time
  and records queue-wait/dispatch spans into each item's trace from its
  worker thread.
- **Dispatch attribution** (the Orca lesson, Yu et al., OSDI '22): a
  coalesced device dispatch is ONE shared span recorded into every
  participating request's trace — same ``dispatch_id``, annotated with
  batch size, the co-batched peers' request ids, bucket shape, padding
  ratio, replica/device, and compile-vs-cached.  The model layer fills
  the bucket/compile fields through :func:`annotate_dispatch_group`, a
  contextvar channel opened around ``speak_batch`` by the scheduler, or
  by the model's own :func:`dispatch_span` when it is called directly
  (the stock path) — no tracer object ever threads through the model
  protocol.
- **One record per device program.**  The model describes every
  back program (decode group) it runs (:func:`annotate_dispatch_group`
  when it is enqueued, :func:`record_device_group` when its result is on
  the host): padded shape, frames each row needed against the bucket the
  plan chose from them, host time per phase (its front batch's wait for
  the frame counts included), bytes handed over from the host at launch.  The record lands in the
  enclosing ``dispatch`` span's ``device_groups`` and, traced or not, in
  :class:`ProgramStats`, the counters ``/metrics`` exports.
- **Spans inside the profiler's trace.**  While ``/debug/profile`` holds
  a ``jax.profiler`` capture, :func:`span` and :func:`annotation` mirror
  the program's boundaries into it as ``sonata:<name>`` events carrying
  ``request_id`` / ``dispatch_id``, so host spans and device operations
  share one file and one clock.
- **Every XLA compile is an event.**  One ``jax.monitoring`` listener a
  process (:func:`install_compile_listener`) turns each top-level trace,
  lowering and backend compile into a record (program, phase, persistent
  cache hit or miss, seconds, thread): counted in :class:`CompileStats`
  (``/metrics``, by warm-up or serving stage), handed to whoever pays for
  it on that thread (:func:`compile_sink`: a device group's, a prefill's,
  a vocoder's, a step group's record gains ``compile_ms`` and
  ``compiled``) and, where a trace is current, recorded as a ``compile``
  span of the request.

Finished traces export three ways:

1. structured JSON log lines when ``SONATA_TRACE_LOG`` is set (truthy =
   via the ``sonata.trace`` logger; a path = appended as JSONL);
2. Chrome trace-event / Perfetto-loadable JSON
   (:meth:`Tracer.chrome_trace`, served at ``/debug/traces?format=chrome``);
3. bounded ring buffers of the N most recent and N slowest traces
   (``SONATA_TRACE_RECENT``/``SONATA_TRACE_SLOWEST``), served from the
   metrics HTTP plane at ``/debug/traces`` and ``/debug/slowest``.

``SONATA_TRACE=0`` disables tracing entirely (default: on; what the
spans cost on a chip, and what a profiler capture costs on top, is
measured in PERF.md, "Where the time goes": the lines "What the spans
cost" and "What a capture costs").  The counters (:class:`ProgramStats`,
:class:`StepStats`, :class:`CompileStats`) stay on either way.
"""

from __future__ import annotations

import contextlib
import contextvars
import heapq
import itertools
import json
import logging
import os
import re
import threading
import time
import uuid
from collections import deque
from typing import Iterator, Optional

from ..utils import profiling
from ..utils.buckets import BATCH_BUCKETS, bucket_for

log = logging.getLogger("sonata.trace")

TRACE_ENV = "SONATA_TRACE"
TRACE_LOG_ENV = "SONATA_TRACE_LOG"
TRACE_RECENT_ENV = "SONATA_TRACE_RECENT"
TRACE_SLOWEST_ENV = "SONATA_TRACE_SLOWEST"
REQUEST_ID_METADATA_KEY = "x-request-id"
DEFAULT_RECENT = 64
DEFAULT_SLOWEST = 32

#: monotonic → wall-clock anchor, fixed at import so every span in a
#: process shares one timebase (Chrome trace ``ts`` must be comparable
#: across traces)
_WALL_ANCHOR = time.time() - time.monotonic()

_ids = itertools.count(1)


def new_id() -> str:
    """Process-unique short id (span/dispatch ids)."""
    return f"{next(_ids):x}"


def new_request_id() -> str:
    """Generated request id for requests that arrived without one."""
    return uuid.uuid4().hex[:16]


def request_id_from_metadata(metadata) -> Optional[str]:
    """Extract ``x-request-id`` from gRPC invocation metadata (a sequence
    of (key, value) pairs), or None."""
    for key, value in metadata or ():
        if str(key).lower() == REQUEST_ID_METADATA_KEY and value:
            return str(value)
    return None


def request_id_from_context(context) -> Optional[str]:
    """``x-request-id`` from a gRPC ServicerContext (or test double)."""
    meta = getattr(context, "invocation_metadata", None)
    if meta is None:
        return None
    try:
        return request_id_from_metadata(meta())
    except Exception:
        return None


#: the one definition of "this env knob is off" (SONATA_TRACE and the
#: SONATA_TRACE_LOG sink check must never diverge on it)
_FALSY = ("0", "false", "off", "no")


def _env_truthy(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    return raw.strip().lower() not in _FALSY


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, default)))
    except ValueError:
        return default


class _NullSpan:
    """Annotation sink for instrumented code running without a trace."""

    __slots__ = ()
    span_id = None

    def annotate(self, **attrs) -> None:
        pass

    def finish(self, end: Optional[float] = None) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One timed stage of a request; belongs to exactly one trace."""

    __slots__ = ("span_id", "parent_id", "name", "start", "end", "attrs")

    def __init__(self, name: str, parent_id: Optional[str],
                 start: Optional[float] = None, attrs: Optional[dict] = None):
        self.span_id = new_id()
        self.parent_id = parent_id
        self.name = name
        self.start = time.monotonic() if start is None else start
        self.end: Optional[float] = None
        self.attrs = dict(attrs) if attrs else {}

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def finish(self, end: Optional[float] = None) -> None:
        if self.end is None:
            self.end = time.monotonic() if end is None else end

    @property
    def duration_s(self) -> Optional[float]:
        if self.end is None:
            return None
        return self.end - self.start

    def to_dict(self, t0: float) -> dict:
        """Serializable view; times relative to the trace root (ms)."""
        d = {"span_id": self.span_id, "parent_id": self.parent_id,
             "name": self.name,
             "start_ms": round((self.start - t0) * 1e3, 3)}
        if self.end is not None:
            d["duration_ms"] = round((self.end - self.start) * 1e3, 3)
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


class Trace:
    """One request's span tree.  Spans may be recorded from any thread."""

    def __init__(self, tracer: "Tracer", name: str, request_id: str,
                 attrs: Optional[dict] = None):
        self._tracer = tracer
        self.name = name
        self.request_id = request_id
        self.attrs = dict(attrs) if attrs else {}
        self.status: Optional[str] = None
        self.wall_start = time.time()
        self._lock = threading.Lock()
        self.root = Span(name, parent_id=None)
        self._spans = [self.root]
        self._finished = False

    # -- recording -----------------------------------------------------------
    def new_span(self, name: str, parent=None,
                 start: Optional[float] = None, end: Optional[float] = None,
                 attrs: Optional[dict] = None) -> Span:
        """Record a span; ``parent`` is a Span, a span id, or None (root).
        Pass ``end`` to record an already-finished interval (how the
        scheduler backfills queue-wait/dispatch from its worker thread)."""
        parent_id = (parent.span_id if isinstance(parent, Span)
                     else parent) or self.root.span_id
        span = Span(name, parent_id, start=start, attrs=attrs)
        if end is not None:
            span.finish(end)
        with self._lock:
            self._spans.append(span)
        return span

    def annotate(self, **attrs) -> None:
        with self._lock:
            self.attrs.update(attrs)

    def finish(self, status: str = "ok") -> None:
        """Idempotent; hands the trace to the tracer's ring buffers."""
        with self._lock:
            if self._finished:
                return
            self._finished = True
            self.status = status
        self.root.finish()
        self._tracer._record(self)

    # -- views ---------------------------------------------------------------
    @property
    def duration_s(self) -> float:
        end = self.root.end if self.root.end is not None else time.monotonic()
        return end - self.root.start

    def spans_snapshot(self) -> list:
        with self._lock:
            return list(self._spans)

    def span_names(self) -> set:
        return {s.name for s in self.spans_snapshot()}

    def to_dict(self) -> dict:
        t0 = self.root.start
        with self._lock:
            spans = list(self._spans)
            attrs = dict(self.attrs)
        return {"request_id": self.request_id, "name": self.name,
                "status": self.status, "wall_start": self.wall_start,
                "duration_ms": round(self.duration_s * 1e3, 3),
                "attrs": attrs,
                "spans": [s.to_dict(t0) for s in spans]}

    def chrome_events(self, tid: int, pid: int = 1) -> list:
        """Chrome trace-event ``X`` (complete) events, one per finished
        span, on one virtual thread per request."""
        events = [{"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                   "args": {"name": f"req {self.request_id}"}}]
        for s in self.spans_snapshot():
            end = s.end if s.end is not None else s.start
            events.append({
                "ph": "X", "pid": pid, "tid": tid, "name": s.name,
                "cat": self.name,
                "ts": round((s.start + _WALL_ANCHOR) * 1e6, 1),
                "dur": round((end - s.start) * 1e6, 1),
                "args": {**s.attrs, "request_id": self.request_id,
                         "span_id": s.span_id,
                         "parent_id": s.parent_id or ""},
            })
        return events


def chrome_events_from_dict(trace_dict: dict, *, pid: int, tid: int = 1,
                            wall_offset_s: float = 0.0) -> list:
    """Chrome trace events from a *serialized* :meth:`Trace.to_dict`
    document — how the sonata-mesh router splices a remote node's trace
    (fetched as JSON over the node's ``/debug/traces?id=`` plane) into
    one stitched cross-host document.

    ``wall_offset_s`` is the probe-measured remote-minus-local wall
    clock offset; subtracting it re-bases the remote spans onto the
    local timebase, matching :meth:`Trace.chrome_events`'s
    wall-anchored ``ts`` so router and node spans line up in one
    Perfetto load."""
    rid = trace_dict.get("request_id", "")
    events = [{"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
               "args": {"name": f"req {rid}"}}]
    t0 = float(trace_dict.get("wall_start", 0.0)) - wall_offset_s
    for s in trace_dict.get("spans", ()):
        start_s = t0 + float(s.get("start_ms", 0.0)) / 1e3
        events.append({
            "ph": "X", "pid": pid, "tid": tid,
            "name": s.get("name", "?"),
            "cat": trace_dict.get("name", ""),
            "ts": round(start_s * 1e6, 1),
            "dur": round(float(s.get("duration_ms", 0.0)) * 1e3, 1),
            "args": {**(s.get("attrs") or {}), "request_id": rid,
                     "span_id": s.get("span_id", ""),
                     "parent_id": s.get("parent_id") or ""},
        })
    return events


# ---------------------------------------------------------------------------
# context propagation (same-thread hooks)
# ---------------------------------------------------------------------------

#: (trace, current_span) for the executing context, or None
_CTX: "contextvars.ContextVar[Optional[tuple]]" = contextvars.ContextVar(
    "sonata_trace_ctx", default=None)


def current() -> Optional[tuple]:
    """The active (trace, span) pair, or None.  What cross-thread stages
    (scheduler items, stream producers) capture at hand-off time."""
    return _CTX.get()


def current_trace() -> Optional[Trace]:
    ctx = _CTX.get()
    return ctx[0] if ctx else None


@contextlib.contextmanager
def use_trace(trace: Optional[Trace], span: Optional[Span] = None
              ) -> Iterator[Optional[Trace]]:
    """Activate ``trace`` (at ``span``, default root) for the block.
    ``trace=None`` is a no-op — callers never need to branch."""
    if trace is None:
        yield None
        return
    token = _CTX.set((trace, span if span is not None else trace.root))
    try:
        yield trace
    finally:
        _reset(token)


def _reset(token) -> None:
    """Reset a context token, tolerating cross-context finalization (a
    generator holding the block can be closed by GC on another thread,
    where the token is foreign and reset() raises ValueError)."""
    try:
        _CTX.reset(token)
    except ValueError:
        pass


#: the request-level spans that :func:`span` mirrors into the profiler's
#: trace while a capture runs: once per request, never per row (the
#: device-group phases go through :func:`annotation`)
PROFILED_SPANS = frozenset(("phonemize", "encode-ids", "stream-emit"))


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator:
    """Record a child span of the current context; no-op without a trace.

    Yields the :class:`Span` (or :data:`NULL_SPAN`), so callers can
    ``sp.annotate(...)`` unconditionally.  An escaping exception is
    recorded as an ``error`` attribute before re-raising.
    """
    ctx = _CTX.get()
    if ctx is None:
        yield NULL_SPAN
        return
    trace, parent = ctx
    sp = trace.new_span(name, parent=parent, attrs=attrs)
    token = _CTX.set((trace, sp))
    try:
        with (profiling.annotation("sonata:" + name,
                                   request_id=trace.request_id)
              if name in PROFILED_SPANS else profiling.NO_ANNOTATION):
            yield sp
    except BaseException as e:
        sp.annotate(error=f"{type(e).__name__}: {e}")
        raise
    finally:
        _reset(token)
        sp.finish()


# ---------------------------------------------------------------------------
# dispatch attribution channel (scheduler ↔ model)
# ---------------------------------------------------------------------------

_DISPATCH: "contextvars.ContextVar[Optional[dict]]" = contextvars.ContextVar(
    "sonata_dispatch_attrs", default=None)


@contextlib.contextmanager
def dispatch_scope(attrs: dict) -> Iterator[dict]:
    """Open the annotation channel for one device dispatch.  The
    scheduler wraps ``model.speak_batch`` in this; the model fills in
    bucket shape / padding / compile state via :func:`annotate_dispatch`
    without knowing anything about tracing."""
    token = _DISPATCH.set(attrs)
    try:
        yield attrs
    finally:
        _DISPATCH.reset(token)


def annotate_dispatch(**attrs) -> None:
    """Attach attributes to the active dispatch span, if any."""
    d = _DISPATCH.get()
    if d is not None:
        d.update(attrs)


def annotate_dispatch_group(**attrs) -> dict:
    """Like :func:`annotate_dispatch`, for models whose one
    ``speak_batch`` call issues SEVERAL device programs (bucket groups).

    Each call appends the group's attrs to ``device_groups``; the span's
    headline fields keep the first group's shape but aggregate the
    outlier-relevant ones worst-case — ``compile`` is ``cold`` if ANY
    group compiled, ``padding_ratio`` is the max — so a cold first group
    followed by cached ones can never be misread as a cached dispatch.

    Returns the group's record (the dict ``device_groups`` holds, or a
    free one when no channel is open), for the model to complete once
    the program's result is on the host and hand to
    :func:`record_device_group`.  The stock path's records are its back
    programs' (one per decode group of the plan): ``plan`` is ``exact``
    (the frame bucket was read off the rows' true frame counts, not
    estimated), and ``front_wait_ms`` says how long the host waited for
    the frame counts of the group's front batch (on the batch's first
    group; 0 on the others).
    """
    d = _DISPATCH.get()
    if d is None:
        return attrs
    groups = d.setdefault("device_groups", [])
    groups.append(attrs)
    if len(groups) == 1:
        d.update(attrs)
        return attrs
    if attrs.get("compile") == "cold":
        d["compile"] = "cold"
    if "padding_ratio" in attrs:
        d["padding_ratio"] = max(d.get("padding_ratio", 0.0),
                                 attrs["padding_ratio"])
    if attrs.get("scaled"):
        # any scaled group puts the whole dispatch outside the warmup
        # lattice's coverage promise (cold-compile containment skips it)
        d["scaled"] = True
    return attrs


def annotation(phase: str):
    """One phase of a device group (``enqueue``, ``fetch``, ``epilogue``)
    as a ``sonata:<phase>`` event in the profiler's trace, carrying the
    open dispatch's ids; a no-op context outside a capture."""
    d = _DISPATCH.get() or {}
    ids = {"dispatch_id": d.get("dispatch_id"),
           "request_id": ",".join(d.get("request_ids", ()))}
    return profiling.annotation(
        "sonata:" + phase, **{k: v for k, v in ids.items() if v})


@contextlib.contextmanager
def dispatch_span(voice: Optional[str] = None, **attrs) -> Iterator:
    """The ``dispatch`` span of a model called with or without a
    scheduler in front of it.

    Under a scheduler's :func:`dispatch_scope` this is :func:`span` and
    nothing else: the scheduler records the shared span and the scope's
    accounting once for the whole call.  Called directly (the stock
    path), the model opens the channel itself: the span gets a
    ``dispatch_id`` and everything the groups annotate, and the dispatch
    is counted exactly once by the installed scope, traced or not, under
    the trace's ``voice`` (else ``voice``, the model's own label).
    """
    if _DISPATCH.get() is not None:
        with span("dispatch", **attrs) as sp:
            yield sp
        return
    chan: dict = {"dispatch_id": new_id()}
    trace = current_trace()
    if trace is not None:
        chan["request_ids"] = [trace.request_id]
        voice = trace.attrs.get("voice") or voice
    if voice:
        chan["voice"] = voice
    t0 = time.monotonic()
    with span("dispatch", **attrs) as sp, dispatch_scope(chan):
        try:
            yield sp
        finally:
            sp.annotate(**chan)
    observer = _DISPATCH_OBSERVER
    if observer is not None:
        observer(time.monotonic() - t0, chan)


#: called with ``(seconds, attrs)`` of every dispatch a model recorded
#: itself (:func:`dispatch_span` outside a scheduler); the scope installs
#: its ``note_dispatch`` here, as it installs the finished-trace hook
_DISPATCH_OBSERVER: Optional[callable] = None


def set_dispatch_observer(fn) -> None:
    global _DISPATCH_OBSERVER
    _DISPATCH_OBSERVER = fn


# ---------------------------------------------------------------------------
# one record per device program (span attributes and always-on counters)
# ---------------------------------------------------------------------------

#: why a frame the device computed was computed; the parts of one program
#: sum to its padded ``batch_bucket * frame_bucket``.  ``headroom`` and
#: ``retried`` are what a frame budget estimated before the program cost;
#: the plan reads true frame counts now, so both stay exported and stay 0
FRAME_PARTS = ("served", "ragged", "headroom", "bucket", "dummy_rows",
               "retried")
HOST_PHASES = ("enqueue", "front_wait", "fetch_wait", "epilogue")


def frame_parts(group: dict) -> dict:
    """The frames one device group computed, split by cause.

    ``served``: what the rows needed (the audio that went out).
    ``ragged``: rows shorter than the group's longest.  ``bucket``: the
    frame bucket above the longest row (the ladder's step).  ``dummy_rows``:
    rows that pad the batch.
    """
    b, n, f = group["batch_bucket"], group["rows"], group["frame_bucket"]
    needs = group["frames_needed"]
    served, longest = sum(needs), max(needs)
    parts = dict.fromkeys(FRAME_PARTS, 0)
    parts.update(served=served, ragged=n * longest - served,
                 bucket=n * (f - longest), dummy_rows=(b - n) * f)
    return parts


class ProgramStats:
    """Process-lifetime counters of the stock path's device programs
    (one record per back program; its front batch's share rides on the
    batch's first record), fed by :func:`record_device_group` whether or not a trace is active
    (one lock and a dozen adds per program) and read by ``/metrics``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._frames = dict.fromkeys(FRAME_PARTS, 0)
        self._host_s = dict.fromkeys(HOST_PHASES, 0.0)
        self._groups = 0
        self._plan_groups = dict.fromkeys(BATCH_BUCKETS, 0)
        self._upload_bytes = 0
        self._frames_per_id: dict = {}

    def record(self, group: dict, voice: Optional[str] = None) -> None:
        parts = frame_parts(group)
        with self._lock:
            self._groups += 1
            # a mesh's rounded batch counts under the ladder step above
            self._plan_groups[min(bucket_for(group["batch_bucket"],
                                             BATCH_BUCKETS),
                                  BATCH_BUCKETS[-1])] += 1
            for part, frames in parts.items():
                self._frames[part] += frames
            for phase in HOST_PHASES:
                self._host_s[phase] += group[phase + "_ms"] / 1e3
            self._upload_bytes += group["upload_bytes"]
            self._frames_per_id[voice or ""] = group["frames_per_id"]

    def snapshot(self) -> dict:
        with self._lock:
            return {"groups": self._groups,
                    "plan_groups": dict(self._plan_groups),
                    "frames": dict(self._frames),
                    "host_seconds": dict(self._host_s),
                    "upload_bytes": self._upload_bytes,
                    "frames_per_id": dict(self._frames_per_id)}

    def frames_per_id(self, voice: str) -> Optional[float]:
        """The frame-budget estimator's value at the voice's latest
        program (``None`` before the first)."""
        with self._lock:
            return self._frames_per_id.get(voice)

    def bind_metrics(self, registry) -> None:
        """Attach the counters to a registry as scrape-time callbacks
        (process-lifetime series: nothing per voice, nothing to tear
        down).  A callback reads one number, which needs no lock."""
        frames = registry.counter(
            "sonata_dispatch_frames_total",
            "Frames computed by the stock path's back programs, by cause: "
            "served (what the rows needed), ragged (rows shorter than "
            "their group's longest), bucket (frame bucket above the "
            "longest row), dummy_rows (batch padding); headroom and "
            "retried stay 0 (no frame budget is estimated, no program "
            "is clipped).  The parts of a program sum to its padded "
            "batch x frames.")
        for part in FRAME_PARTS:
            frames.labels(part=part).set_function(
                lambda p=part: float(self._frames[p]))
        host = registry.counter(
            "sonata_dispatch_host_seconds_total",
            "Host seconds per phase of a device program: enqueue (pad, "
            "transfer, asynchronous dispatch, of a front batch and of "
            "its groups), front_wait (blocked on a front batch's frame "
            "counts, before the plan), fetch_wait (blocked on the "
            "result), epilogue (dequantise and slice).")
        for phase in HOST_PHASES:
            host.labels(phase=phase).set_function(
                lambda p=phase: self._host_s[p])
        registry.counter(
            "sonata_dispatch_upload_bytes_total",
            "Bytes device programs were handed from the host at launch: "
            "ids, lengths, scales and the key, a few kilobytes a "
            "program; a voice's weights only if they were not resident."
        ).set_function(lambda: float(self._upload_bytes))
        registry.counter(
            "sonata_dispatch_groups_total",
            "Decode groups finished (one back program each)."
        ).set_function(lambda: float(self._groups))
        registry.counter(
            "sonata_dispatch_overflow_retries_total",
            "Device groups whose frame bucket was too small for a row: "
            "always 0 since the plan reads the rows' true frame counts "
            "(a group's bucket holds its longest row by construction)."
        ).set_function(lambda: 0.0)
        plan = registry.counter(
            "sonata_dispatch_plan_groups_total",
            "Decode groups by the padded batch they ran at: how often "
            "the plan split a front batch, and into what.")
        for b in BATCH_BUCKETS:
            plan.labels(rows=str(b)).set_function(
                lambda b=b: float(self._plan_groups[b]))


_program_stats = ProgramStats()


def program_stats() -> ProgramStats:
    """The process's one :class:`ProgramStats` (programs are counted
    where they run, whichever runtime or caller started them)."""
    return _program_stats


def record_device_group(group: dict, voice: Optional[str] = None) -> None:
    """A device group's program has finished and ``group`` (the record
    :func:`annotate_dispatch_group` returned) is complete: count it."""
    _program_stats.record(group, voice)


# ---------------------------------------------------------------------------
# step-wise generation: what a step loop records (spans and counters)
# ---------------------------------------------------------------------------

#: host phases of a step loop's iteration, none of them blocked on the
#: device: ``launch`` (the step program's asynchronous call), ``admit``
#: (prefills enqueued), ``retire`` (vocoder programs enqueued)
AR_HOST_PHASES = ("launch", "admit", "retire")
#: the rest of a turn of the loop (launch to launch), a step group's
#: attributes of their own (``<phase>_ms``): ``device_wait`` (blocked until
#: the step before has run: the one sign inside the program of whether host
#: or device is the clock), ``record`` (the rest of settling that step:
#: sums, closing a group, finishing the loop's trace with its exporters and
#: observers) and ``other`` (the turn less all of these: the condition's
#: lock, the garbage collector)
AR_SETTLE_PHASES = ("device_wait", "record", "other")
#: every phase of a turn; a turn's phases sum to its wall time
AR_TURN_PHASES = AR_HOST_PHASES + AR_SETTLE_PHASES
#: bounds of ``sonata_ar_turn_seconds``: a step is 5-20 ms; a stall is
#: what lands at 0.1 s and above
AR_TURN_BUCKETS_S = (0.005, 0.01, 0.02, 0.03, 0.05, 0.1, 0.25, 0.5, 1.0,
                     2.5, 5.0, 10.0)
#: what a step or prefill program's expert products run (a span's
#: ``expert_matmul``)
EXPERT_MATMULS = ("grouped", "ragged_dot")
#: what reads the slots' keys and values in a step program, and a prompt's
#: own in a prefill program (a span's ``attention``)
ATTENTION_IMPLS = ("slot_kernel", "einsum")
#: the gauges of what the slots of step loops hold on the device, as
#: ``/metrics`` renders them: a loop's engine says its bytes by these names
RESIDENT_SERIES = ("sonata_ssm_state_resident_bytes",
                   "sonata_delta_state_resident_bytes",
                   "sonata_mla_cache_resident_bytes",
                   'sonata_attn_cache_resident_bytes{kind="full"}',
                   'sonata_attn_cache_resident_bytes{kind="ring"}')


def held_load(load) -> tuple:
    """Of one expert layer's load (``unit_layers.moe_ffn``): the distinct
    experts chosen among those the chip holds and the assignments that fell
    on them; a layer that holds every expert reports neither apart."""
    return (load[3], load[4]) if len(load) > 3 else (load[0], load[2])


def held_overflow(loads) -> bool:
    """Whether, in a launch of these loads (one an expert layer), a thin
    share's held experts got more rows than the program's short path
    takes in any layer (``unit_layers.moe_ffn``'s sixth number; a layer that
    is not thin states none)."""
    return any(len(load) > 5 and int(load[5]) for load in loads)


class StepStats:
    """Process-lifetime counters of step-wise generation (a voice's step
    loop: :mod:`sonata_tpu.synth.steploop`), fed by the loop whether or not
    a trace is active and read by ``/metrics``.  ``layers`` are the expert
    layers' indices in the backbone, known once a loop has started."""

    def __init__(self):
        self._lock = threading.Lock()
        self.steps = 0
        self.units = 0
        self.row_passes = {"denoise": 0, "commit": 0}
        self.slot_steps = {"live": 0, "empty": 0}
        self.prefill_tokens = 0
        self.rows = {"admitted": 0, "retired": 0}
        #: rows admitted by ``(voice, how)``: ``step`` (the prompt rode a
        #: step) or ``apart`` (a prefill program of its own)
        self.admits: dict = {}
        self.host_s = dict.fromkeys(AR_TURN_PHASES, 0.0)
        #: wall seconds of each turn of a loop that launched a step
        self.turns = profiling.Histogram(AR_TURN_BUCKETS_S)
        #: per expert layer: assignments, distinct experts summed over
        #: steps, the fullest expert's assignments summed over steps, the
        #: assignments that fell on experts the chip holds
        self.moe: dict = {}
        #: bytes the loops' slots hold on the device while they live, by the
        #: series that exports them (a loop's engine says which, and how
        #: many: :meth:`record_resident`)
        self.resident = dict.fromkeys(RESIDENT_SERIES, 0)
        #: places a layer's latent reader moved for the live rows (whole
        #: chunks up to a row's length; every position under the einsum)
        self.mla_places_fetched = 0
        #: places the reader of keys and values a head moved for the live
        #: rows, over the layers that keep such (whole chunks up to a row's
        #: length, a ring's window at most; every place under the einsum)
        self.kv_places_fetched = 0
        #: live rows' steps whose position was at or past the window
        self.window_bound_row_steps = 0
        #: step launches that took the expert layer's full-length path
        #: because a thin share's held experts got more than the short one
        #: takes
        self.held_overflow_steps = 0
        #: launches by what their expert products ran and by program
        self.expert_matmul = {(impl, program): 0
                              for impl in EXPERT_MATMULS
                              for program in ("step", "prefill")}
        #: launches by what their attention ran and by program
        self.attention = {(impl, program): 0
                          for impl in ATTENTION_IMPLS
                          for program in ("step", "prefill")}
        self.slots_in_use = 0
        self._registry = None

    def record_steps(self, group: dict) -> None:
        """A group of steps (the attributes of its ``dispatch`` span)."""
        with self._lock:
            self.steps += group["steps"]
            self.units += group["units"]
            self.row_passes["denoise"] += group["denoise_row_passes"]
            self.row_passes["commit"] += group["commit_row_passes"]
            self.expert_matmul[group["expert_matmul"], "step"] += group[
                "steps"]
            self.attention[group["attention"], "step"] += group["steps"]
            self.held_overflow_steps += group.get("held_overflow_steps", 0)
            self.mla_places_fetched += group.get("latent_places_fetched", 0)
            self.kv_places_fetched += group.get("kv_places_fetched", 0)
            self.window_bound_row_steps += group.get(
                "window_bound_row_steps", 0)
            self.slot_steps["live"] += group["live_slot_steps"]
            self.slot_steps["empty"] += (group["steps"] * group["slots"]
                                         - group["live_slot_steps"])
            for phase in AR_HOST_PHASES:
                self.host_s[phase] += group["host_ms"][phase] / 1e3
            for phase in AR_SETTLE_PHASES:
                self.host_s[phase] += group[phase + "_ms"] / 1e3
            self._add_loads(group["layers"], group["assignments"],
                            group["experts_touched"],
                            group["max_expert_assignments"],
                            group["held_assignments"])

    def record_prefill(self, tokens: int, layers, loads, units: int = 0,
                       expert_matmul: str = "ragged_dot",
                       attention: str = "einsum", voice: str = "") -> None:
        """One row admitted by a prefill program of its own: its prompt's
        tokens, what they chose, the units the prefill itself gave the row,
        and what its program's expert products and its attention ran."""
        with self._lock:
            self.expert_matmul[expert_matmul, "prefill"] += 1
            self.attention[attention, "prefill"] += 1
            self._add_loads(layers, [int(l[2]) for l in loads],
                            [int(l[0]) for l in loads],
                            [int(l[1]) for l in loads],
                            [int(held_load(l)[1]) for l in loads])
            self._admitted(tokens, units, voice, "apart")

    def record_admit_step(self, tokens: int, units: int = 0,
                          voice: str = "") -> None:
        """One row admitted in a step: its prompt's tokens and the units
        the launch gave the row.  What the prompt chose is in the step's
        load, and no program of its own ran."""
        with self._lock:
            self._admitted(tokens, units, voice, "step")

    def _admitted(self, tokens: int, units: int, voice: str,
                  how: str) -> None:
        self.prefill_tokens += tokens
        self.units += units
        self.rows["admitted"] += 1
        key = (voice, how)
        if key not in self.admits:
            self.admits[key] = 0
            if self._registry is not None:
                self._bind_admits([key])
        self.admits[key] += 1

    def _add_loads(self, layers, assignments, touched, fullest,
                   held) -> None:
        new = [layer for layer in layers if layer not in self.moe]
        for layer, *sums in zip(layers, assignments, touched, fullest, held):
            totals = self.moe.setdefault(layer, [0, 0, 0, 0])
            for k, value in enumerate(sums):
                totals[k] += value
        if new and self._registry is not None:
            self._bind_layers(new)

    def record_resident(self, held: dict, let_go: bool = False) -> None:
        """A loop's slots were made (or let go): the bytes they hold, by
        the series that exports them."""
        with self._lock:
            for series, held_bytes in held.items():
                self.resident[series] = self.resident.get(series, 0) + (
                    -held_bytes if let_go else held_bytes)

    def record_retired(self) -> None:
        with self._lock:
            self.rows["retired"] += 1

    def bind_metrics(self, registry) -> None:
        """Scrape-time callbacks, as :meth:`ProgramStats.bind_metrics`;
        the expert layers' series appear with the first loop's first
        record (a process that serves no such voice exports none)."""
        registry.counter(
            "sonata_ar_steps_total",
            "Step programs run by step-wise generation loops (one step "
            "runs every slot's current block: one token, or one pass over "
            "block_length positions)."
        ).set_function(lambda: float(self.steps))
        registry.counter(
            "sonata_ar_units_total",
            "Units rows were left with by prefill and step programs (a "
            "step is not a unit: a backbone that denoises blocks gives a "
            "block of units every few steps, and none between)."
        ).set_function(lambda: float(self.units))
        row_passes = registry.counter(
            "sonata_diff_row_passes_total",
            "Live rows' launches of step programs, by phase: commit (the "
            "last pass over a block: its units are the row's, its keys and "
            "values stay; every launch of a backbone that decodes token by "
            "token) or denoise (a pass that leaves the block unfinished).")
        for phase in ("denoise", "commit"):
            row_passes.labels(phase=phase).set_function(
                lambda p=phase: float(self.row_passes[p]))
        slot_steps = registry.counter(
            "sonata_ar_slot_steps_total",
            "Slots computed by step programs, by state: live (a row's "
            "block: one token, or block_length positions) or empty (masked "
            "padding of the static shape).")
        for state in ("live", "empty"):
            slot_steps.labels(state=state).set_function(
                lambda s=state: float(self.slot_steps[s]))
        registry.counter(
            "sonata_ar_prefill_tokens_total",
            "Prompt tokens run by prefill programs and by steps that "
            "carried an arrival."
        ).set_function(lambda: float(self.prefill_tokens))
        rows = registry.counter(
            "sonata_ar_rows_total",
            "Rows (sentences) of step-wise generation, by event: admitted "
            "(its prompt run into a slot) or retired (frame budget reached, "
            "units handed to the vocoder).")
        for event in ("admitted", "retired"):
            rows.labels(event=event).set_function(
                lambda e=event: float(self.rows[e]))
        host = registry.counter(
            "sonata_ar_host_seconds_total",
            "Seconds of a step loop's turns by phase.  Not blocked on the "
            "device: launch (the step's asynchronous call), admit (prefills "
            "enqueued), retire (vocoder programs enqueued), record (sums, "
            "spans, the loop's trace handed to its exporters), other (the "
            "turn less its phases: locks, the garbage collector).  Blocked "
            "on it: device_wait (until the step before has run); its share "
            "of the sum falls as the loop turns host-bound.")
        for phase in AR_TURN_PHASES:
            host.labels(phase=phase).set_function(
                lambda p=phase: self.host_s[p])
        registry.histogram(
            "sonata_ar_turn_seconds",
            "Wall seconds of one turn of a step loop, launch to launch "
            "(admit, launch, retire, wait for the step before, record): a "
            "stall of the loop's thread is a count at 0.1 s and above.",
            buckets=AR_TURN_BUCKETS_S).attach(self.turns)
        launches = registry.counter(
            "sonata_moe_expert_matmul_total",
            "Launches of step and prefill programs, by what their expert "
            "products run: grouped (this repo's kernel: "
            "sonata_tpu/ops/grouped_matmul.py) or ragged_dot (XLA's; every "
            "launch off a TPU, and the shapes the kernel's tile rule "
            "leaves to it).")
        for impl, program in self.expert_matmul:
            launches.labels(impl=impl, program=program).set_function(
                lambda k=(impl, program): float(self.expert_matmul[k]))
        attention = registry.counter(
            "sonata_attention_impl_total",
            "Launches of step and prefill programs, by what their "
            "attention runs: slot_kernel (this repo's kernel over the "
            "slots' keys and values: sonata_tpu/ops/slot_attention.py) or "
            "einsum (XLA's products: every launch off a TPU, the shapes "
            "the kernel's tile rule leaves to it, and every prefill, which "
            "attends over its own prompt).")
        for impl, program in self.attention:
            attention.labels(impl=impl, program=program).set_function(
                lambda k=(impl, program): float(self.attention[k]))
        registry.gauge(
            "sonata_ar_slots_in_use",
            "Slots of step-wise generation loops that hold a row."
        ).set_function(lambda: float(self.slots_in_use))
        registry.gauge(
            "sonata_ssm_state_resident_bytes",
            "Bytes of recurrent state and convolution columns the slots of "
            "step-wise generation loops hold on the device (every slot, "
            "live or not: such state does not grow with a row; 0 for a "
            "backbone that has none)."
        ).set_function(lambda: float(
            self.resident["sonata_ssm_state_resident_bytes"]))
        registry.gauge(
            "sonata_delta_state_resident_bytes",
            "Bytes of delta-rule state (linear attention: a matrix a value "
            "head and layer) and convolution columns the slots of step-wise "
            "generation loops hold on the device (every slot, live or not: "
            "such state does not grow with a row; 0 for a backbone that has "
            "none)."
        ).set_function(lambda: float(
            self.resident["sonata_delta_state_resident_bytes"]))
        registry.gauge(
            "sonata_mla_cache_resident_bytes",
            "Bytes of latent rows the slots of step-wise generation loops "
            "hold on the device (latent attention: one row a position and "
            "layer, keys and values at once, in whole lanes; every slot and "
            "position, live or not; 0 for a backbone that has none)."
        ).set_function(lambda: float(
            self.resident["sonata_mla_cache_resident_bytes"]))
        registry.counter(
            "sonata_mla_places_fetched_total",
            "Places a layer's latent reader moved for the live rows of "
            "step launches: a row's places in whole chunks of the kernel's "
            "up to its length, every position where the einsum reads (a "
            "step group's kv_positions over its latent_places_fetched is "
            "the share of what was moved that a row held; 0 for a backbone "
            "without latent attention)."
        ).set_function(lambda: float(self.mla_places_fetched))
        registry.counter(
            "sonata_kv_places_fetched_total",
            "Places the reader of the slots' keys and values moved for the "
            "live rows of step launches, summed over the layers that keep "
            "keys and values a head: a row's places in whole chunks of the "
            "kernel's up to its length, a ring read no further than its "
            "window, every place of a layer's buffer where the einsum "
            "reads (a step group's kv_positions a layer, or its "
            "kv_cache_bytes over a place's bytes, over its "
            "kv_places_fetched is the share of what was moved that a row "
            "held; 0 for a backbone whose cache is latent rows)."
        ).set_function(lambda: float(self.kv_places_fetched))
        resident = registry.gauge(
            "sonata_attn_cache_resident_bytes",
            "Bytes of keys and values the slots of step-wise generation "
            "loops hold on the device in a backbone that mixes full and "
            "window attention, by kind of layer: full (every position of "
            "every slot) or ring (window places a slot: position p lies at "
            "place p mod window); 0 for a backbone without window layers.")
        for kind in ("full", "ring"):
            resident.labels(kind=kind).set_function(
                lambda k=kind: float(self.resident[
                    'sonata_attn_cache_resident_bytes{kind="%s"}' % k]))
        registry.counter(
            "sonata_attn_window_bound_row_steps_total",
            "Live rows' steps whose position was at or past the window of "
            "the backbone's window layers: the ring had wrapped and the "
            "band, not the row's length, bounded what those layers read "
            "(over sonata_ar_slot_steps_total{state=\"live\"}: the share "
            "of row-steps the window binds; 0 for a backbone without "
            "window layers)."
        ).set_function(lambda: float(self.window_bound_row_steps))
        registry.counter(
            "sonata_moe_held_overflow_steps_total",
            "Step launches whose expert layer took its full-length path "
            "because the experts this chip holds (a thin share of the "
            "router's) got more assignment rows than the program's short "
            "path takes; no held assignment is ever left out."
        ).set_function(lambda: float(self.held_overflow_steps))
        self._registry = registry
        with self._lock:
            self._bind_layers(list(self.moe))
            self._bind_admits(list(self.admits))

    def _bind_admits(self, keys) -> None:
        metric = self._registry.counter(
            "sonata_ar_admits_total",
            "Rows admitted by step-wise generation loops, by voice and by "
            "how the prompt ran: step (it rode the step it arrived beside: "
            "one launch streamed the experts once for the live rows and "
            "the prompt) or apart (a prefill program of its own, between "
            "two steps).")
        for voice, how in keys:
            metric.labels(voice=voice, how=how).set_function(
                lambda k=(voice, how): float(self.admits[k]))

    def _bind_layers(self, layers) -> None:
        r = self._registry
        series = (
            ("sonata_moe_assignments_total",
             "Token-to-expert assignments of an expert layer (prefill and "
             "steps)."),
            ("sonata_moe_experts_touched_total",
             "Distinct experts chosen in an expert layer, summed over its "
             "programs (over sonata_ar_steps_total: experts a step reads)."),
            ("sonata_moe_max_expert_assignments_total",
             "Assignments of the fullest expert of an expert layer, summed "
             "over its programs (over assignments: the load's skew)."),
            ("sonata_moe_held_assignments_total",
             "Assignments of an expert layer that fell on experts this chip "
             "holds (over assignments: the share of the layer's routed work "
             "done here; all of them where the chip holds every expert)."))
        for k, (name, text) in enumerate(series):
            metric = r.counter(name, text)
            for layer in layers:
                metric.labels(layer=str(layer)).set_function(
                    lambda l=layer, k=k: float(self.moe[l][k]))


_step_stats = StepStats()


def step_stats() -> StepStats:
    """The process's one :class:`StepStats`."""
    return _step_stats


# ---------------------------------------------------------------------------
# compile events: which program compiled, when, for how long, on which thread
# ---------------------------------------------------------------------------

#: ``jax.monitoring``'s time spans of a program's way to an executable
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend"}
#: events JAX fires inside a backend span, and what they say of the
#: persistent cache: asked (and, unless a hit follows, compiled: an entry
#: is written only above the cache's thresholds), loaded, written
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss"}
COMPILE_STAGES = ("warmup", "serving")
#: distinct program names counted apart (jitted programs are a few dozen;
#: eager operations keep their own); the rest count as ``other``
MAX_COMPILE_PROGRAMS = 256

#: per thread: ``depth`` of open compile spans, ``cache`` (what the open
#: backend span has heard of the persistent cache), ``sink`` (the list of
#: whoever pays for what compiles here, or None)
_compiling = threading.local()


def _program_name(fun_name) -> str:
    """``fun_name`` without its ``jit(...)``, safe as a label value."""
    name = str(fun_name or "unknown")
    wrapped = re.match(r"^\w+\((.*)\)$", name)
    if wrapped:
        name = wrapped.group(1)
    return re.sub(r"[^\w.<>:-]", "_", name)[:64] or "unknown"


class CompileStats:
    """Process-lifetime counters of XLA compiles, fed by the listener of
    :func:`install_compile_listener` and read by ``/metrics``: how many and
    how many seconds, by program, phase (``trace`` | ``lower`` |
    ``backend``), persistent cache (``hit`` | ``miss`` | ``off``: the backend
    phase's; a trace or a lowering consults none and says ``off``) and
    stage (``warmup`` until the scope's ``mark_warmup_complete``,
    ``serving`` after)."""

    def __init__(self):
        self._lock = threading.Lock()
        #: set by the installed scope (``scope._set_compile_stage``)
        self.stage = COMPILE_STAGES[0]
        #: (program, phase, cache, stage) -> [count, seconds]
        self._totals: dict = {}
        self._programs: set = set()
        self._registry = None

    def record(self, record: dict) -> None:
        program = record["program"]
        with self._lock:
            if program not in self._programs:
                if len(self._programs) >= MAX_COMPILE_PROGRAMS:
                    program = "other"
                self._programs.add(program)
            key = (program, record["phase"], record["cache"], self.stage)
            totals = self._totals.get(key)
            if totals is None:
                totals = self._totals[key] = [0, 0.0]
                if self._registry is not None:
                    self._bind(key)
            totals[0] += 1
            totals[1] += record["seconds"]

    def snapshot(self) -> dict:
        """``{(program, phase, cache, stage): (count, seconds)}``."""
        with self._lock:
            return {k: tuple(v) for k, v in self._totals.items()}

    def bind_metrics(self, registry) -> None:
        """Scrape-time callbacks, as :meth:`ProgramStats.bind_metrics`; a
        series appears with its first compile."""
        with self._lock:
            self._registry = registry
            for key in self._totals:
                self._bind(key)

    def _bind(self, key: tuple) -> None:
        labels = dict(zip(("program", "phase", "cache", "stage"), key))
        r = self._registry
        r.counter(
            "sonata_compile_total",
            "XLA compiles by program (the jitted function's name; an eager "
            "operation's own), phase (trace, lower, backend), persistent "
            "cache (hit: loaded; miss: compiled; off: not asked) and stage "
            "(warmup until the boot warm-up completed, serving after: "
            "whatever compiles then holds a serving thread)."
        ).labels(**labels).set_function(
            lambda k=key: float(self._totals[k][0]))
        r.counter(
            "sonata_compile_seconds_total",
            "Seconds the compiles of sonata_compile_total took, on the "
            "thread that asked (nested traces count in their parent's)."
        ).labels(**labels).set_function(
            lambda k=key: self._totals[k][1])


_compile_stats = CompileStats()


def compile_stats() -> CompileStats:
    """The process's one :class:`CompileStats`."""
    return _compile_stats


def _on_compile_start(event: str, value=None, **kwargs) -> None:
    # JAX records a span's start as a scalar: what tells a nested span
    # (a jitted function traced inside another's trace) from its parent
    if event in COMPILE_PHASES:
        _compiling.depth = getattr(_compiling, "depth", 0) + 1
        if COMPILE_PHASES[event] == "backend":
            _compiling.cache = "off"


def _on_cache_event(event: str, **kwargs) -> None:
    state = _CACHE_EVENTS.get(event)
    if state is not None:
        _compiling.cache = state


def _on_compile_span(event: str, start: float, end: float,
                     **kwargs) -> None:
    phase = COMPILE_PHASES.get(event)
    if phase is None:
        return
    depth = _compiling.depth = max(getattr(_compiling, "depth", 1) - 1, 0)
    if depth:
        return      # nested: its time is its parent's
    try:
        # JAX reads time.time(); the spans' clock is the monotonic one
        anchor = profiling.clock_anchor()
        shift = anchor["monotonic"] - anchor["wall"]
        record = {
            "program": _program_name(kwargs.get("fun_name")), "phase": phase,
            "cache": (getattr(_compiling, "cache", "off")
                      if phase == "backend" else "off"),
            "seconds": end - start, "start": start + shift,
            "end": end + shift, "thread": threading.current_thread().name}
        _compile_stats.record(record)
        sink = getattr(_compiling, "sink", None)
        if sink is not None:
            sink.append(record)
        ctx = _CTX.get()
        if ctx is not None:
            ctx[0].new_span(
                "compile", parent=ctx[1], start=record["start"],
                end=record["end"],
                attrs={k: record[k] for k in ("program", "phase", "cache")})
    except Exception:   # a listener must never break a compile
        log.exception("compile listener failed")


_compile_listener_installed = False


def install_compile_listener() -> None:
    """Register the listener with ``jax.monitoring``, once a process
    (called where the persistent cache is enabled: every long-lived entry
    point starts there, before its first compile)."""
    global _compile_listener_installed
    with _default_lock:
        if _compile_listener_installed:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_compile_start)
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_time_span_listener(_on_compile_span)
        _compile_listener_installed = True


@contextlib.contextmanager
def compile_sink() -> Iterator[list]:
    """Collect the records of what compiles on this thread inside the
    block: whoever closes a record of the work that paid for a compile
    opens one around it and reads the list (:func:`compile_attrs`).  An
    inner block takes what compiles under it; the outer one never sees
    that, and no other thread's."""
    outer = getattr(_compiling, "sink", None)
    sink = _compiling.sink = []
    try:
        yield sink
    finally:
        _compiling.sink = outer


def compile_attrs(paid: list) -> dict:
    """What a record gains from the compiles it paid for, which are taken
    off ``paid``: ``compile_ms`` and ``compiled`` (the programs' names), or
    nothing where nothing compiled."""
    if not paid:
        return {}
    records, paid[:] = list(paid), []
    return {"compile_ms": round(sum(r["seconds"] for r in records) * 1e3, 3),
            "compiled": sorted({r["program"] for r in records})}


def launch_compile(paid: list) -> dict:
    """The ``compile`` of a launch from what ran under it: ``cold`` where
    a backend compile or a cache load did, else ``cached``; with
    ``compile_ms`` and ``compiled`` where anything compiled."""
    # a load from the persistent cache holds the thread as a compile does
    cold = any(r["phase"] == "backend" for r in paid)
    return dict(compile_attrs(paid), compile="cold" if cold else "cached")


# ---------------------------------------------------------------------------
# finished-trace observer (the scope aggregation plane's feed)
# ---------------------------------------------------------------------------

#: one process-wide hook called with every finished Trace (whatever
#: tracer finished it, so injected test tracers feed the same plane).
#: None (the default) keeps trace finish exactly as cheap as before —
#: a single module-global read.
_TRACE_OBSERVER: Optional[callable] = None


def set_trace_observer(fn) -> None:
    """Install (or clear, with None) the finished-trace hook.  What
    :mod:`.scope` uses to feed per-stage quantile sketches without the
    tracer knowing the aggregation plane exists."""
    global _TRACE_OBSERVER
    _TRACE_OBSERVER = fn


# ---------------------------------------------------------------------------
# tracer: ring buffers + exports
# ---------------------------------------------------------------------------

class Tracer:
    """Owns finished-trace retention and export; cheap to share.

    ``enabled=False`` (or ``SONATA_TRACE=0``) turns :meth:`start_trace`
    into a None factory — every downstream hook then no-ops.
    """

    def __init__(self, *, enabled: Optional[bool] = None,
                 recent: Optional[int] = None,
                 slowest: Optional[int] = None,
                 log_sink: Optional[str] = None):
        self.enabled = (_env_truthy(TRACE_ENV, True)
                        if enabled is None else enabled)
        self.recent_cap = recent or _env_int(TRACE_RECENT_ENV,
                                             DEFAULT_RECENT)
        self.slowest_cap = slowest or _env_int(TRACE_SLOWEST_ENV,
                                               DEFAULT_SLOWEST)
        self._recent: "deque[Trace]" = deque(maxlen=self.recent_cap)
        self._slow: list = []  # min-heap of (duration, seq, trace)
        self._seq = itertools.count()
        self._lock = threading.Lock()
        #: SONATA_TRACE_LOG: truthy → JSON line per trace via the
        #: ``sonata.trace`` logger; a path-looking value → append JSONL
        raw = (os.environ.get(TRACE_LOG_ENV, "")
               if log_sink is None else log_sink).strip()
        self._log_path: Optional[str] = None
        self._log_lock = threading.Lock()  # file appends only: disk I/O
        #                must never block the ring buffers or /debug reads
        self._log_lines = False
        if raw and raw.lower() not in _FALSY:
            if os.sep in raw or raw.endswith((".jsonl", ".json", ".log")):
                self._log_path = raw
            else:
                self._log_lines = True

    # -- trace lifecycle -----------------------------------------------------
    def start_trace(self, name: str, request_id: Optional[str] = None,
                    **attrs) -> Optional[Trace]:
        if not self.enabled:
            return None
        return Trace(self, name, request_id or new_request_id(), attrs)

    @contextlib.contextmanager
    def trace_request(self, name: str, request_id: Optional[str] = None,
                      **attrs) -> Iterator[Optional[Trace]]:
        """Create + activate a trace for the block; finishes it with
        ``ok`` or ``error: <type>`` (exceptions re-raise)."""
        trace = self.start_trace(name, request_id=request_id, **attrs)
        if trace is None:
            yield None
            return
        with use_trace(trace):
            try:
                yield trace
            except BaseException as e:
                trace.annotate(error=str(e))
                trace.finish(status=f"error: {type(e).__name__}")
                raise
            else:
                trace.finish("ok")

    def _record(self, trace: Trace) -> None:
        duration = trace.duration_s
        with self._lock:
            self._recent.append(trace)
            entry = (duration, next(self._seq), trace)
            if len(self._slow) < self.slowest_cap:
                heapq.heappush(self._slow, entry)
            elif duration > self._slow[0][0]:
                heapq.heapreplace(self._slow, entry)
        if self._log_lines or self._log_path:
            self._export_log_line(trace)
        observer = _TRACE_OBSERVER
        if observer is not None:
            try:
                observer(trace)
            except Exception:
                # the aggregation plane must never break trace retention
                log.exception("trace observer failed")

    def _export_log_line(self, trace: Trace) -> None:
        try:
            line = json.dumps({"event": "trace", **trace.to_dict()},
                              ensure_ascii=False,
                              separators=(",", ":"))
        except (TypeError, ValueError):
            # a non-serializable attr must never break the request path
            log.exception("trace %s not JSON-serializable",
                          trace.request_id)
            return
        if self._log_path:
            try:
                with self._log_lock:
                    with open(self._log_path, "a", encoding="utf-8") as f:
                        f.write(line + "\n")
            except OSError:
                log.exception("cannot append to %s", self._log_path)
        else:
            log.info("%s", line)

    # -- retrieval -----------------------------------------------------------
    def recent_traces(self) -> list:
        """Finished traces, newest first."""
        with self._lock:
            return list(self._recent)[::-1]

    def slowest_traces(self) -> list:
        """Finished traces, slowest first (bounded ring)."""
        with self._lock:
            entries = sorted(self._slow, reverse=True)
        return [t for _d, _s, t in entries]

    def find(self, request_id: str) -> Optional[Trace]:
        for t in self.recent_traces():
            if t.request_id == request_id:
                return t
        return None

    def clear(self) -> None:
        with self._lock:
            self._recent.clear()
            self._slow.clear()

    # -- exports -------------------------------------------------------------
    @staticmethod
    def chrome_trace(traces) -> dict:
        """Chrome trace-event JSON (load in chrome://tracing or
        https://ui.perfetto.dev): one virtual thread per request."""
        events = []
        for tid, trace in enumerate(traces, start=1):
            events.extend(trace.chrome_events(tid))
        return {"traceEvents": events, "displayTimeUnit": "ms"}


_default_tracer: Optional[Tracer] = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    """Process-wide tracer (what :class:`ServingRuntime` and the CLI use
    by default, so the HTTP debug plane and every frontend agree on one
    ring buffer)."""
    global _default_tracer
    if _default_tracer is None:
        with _default_lock:
            if _default_tracer is None:
                _default_tracer = Tracer()
    return _default_tracer
