"""Batching core + iteration-level scheduling (SONATA_BATCH_MODE).

The PR-10 tentpole: ONE gather/dispatch engine
(:mod:`sonata_tpu.synth.batching`) behind the batch scheduler and both
stream coalescers, plus the Orca-style persistent iteration loop.  The
join/retire contract pins here:

- a stream joins the running batch mid-flight at an iteration boundary
  and retires without recompiling anything;
- deadline expiry mid-flight fails only the expired stream;
- drain retires the loop at an iteration boundary;
- a breaker trip on a pool replica resubmits iteration-mode requests
  exactly once (the pool machinery is mode-agnostic);
- the degradation ladder forces iteration back to dispatch mode.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from sonata_tpu.core import OperationError
from sonata_tpu.serving import Deadline, DeadlineExceeded, degradation_mod
from sonata_tpu.synth.batching import (
    BatchingCore,
    IterationLoop,
    SchedulerCrashed,
    WorkItem,
    effective_batch_mode,
    resolve_batch_mode,
)

from voices import tiny_voice


# ---------------------------------------------------------------------------
# mode resolution
# ---------------------------------------------------------------------------

class _Policy:
    def __init__(self, coalesce):
        self.coalesce = coalesce


def test_batch_mode_env_wins_over_policy():
    assert resolve_batch_mode(_Policy(True),
                              env={"SONATA_BATCH_MODE": "dispatch"}) \
        == "dispatch"
    assert resolve_batch_mode(_Policy(False),
                              env={"SONATA_BATCH_MODE": "iteration"}) \
        == "iteration"


def test_batch_mode_defaults_from_dispatch_policy():
    # the PR-1 probe decision carries: coalescing backends get the
    # persistent loop, per-request backends keep wave dispatch
    assert resolve_batch_mode(_Policy(True), env={}) == "iteration"
    assert resolve_batch_mode(_Policy(False), env={}) == "dispatch"
    assert resolve_batch_mode(None, env={}) == "dispatch"


def test_batch_mode_typo_fails_loudly():
    with pytest.raises(OperationError, match="SONATA_BATCH_MODE"):
        resolve_batch_mode(None, env={"SONATA_BATCH_MODE": "itreation"})


def test_degradation_forces_dispatch_mode():
    class _Ladder:
        level = 0

        def current_level(self):
            return self.level

    ladder = _Ladder()
    degradation_mod.install(ladder)
    try:
        env = {"SONATA_BATCH_MODE": "iteration"}
        assert effective_batch_mode(None, env) == "iteration"
        ladder.level = 1  # shrink-coalesce: same threshold as the
        # gather-window collapse
        assert effective_batch_mode(None, env) == "dispatch"
        ladder.level = 0  # hysteresis recovery re-admits the loop
        assert effective_batch_mode(None, env) == "iteration"
    finally:
        degradation_mod.uninstall(ladder)


# ---------------------------------------------------------------------------
# the core engine (fake dispatch; no device)
# ---------------------------------------------------------------------------

def test_core_keyed_grouping_requeues_leftovers():
    """Mixed-key items split into homogeneous dispatch groups; the
    incompatible leftovers ride the next wave instead of being lost."""
    groups = []
    done = threading.Event()

    def dispatch(items):
        groups.append([i.key for i in items])
        for i in items:
            i.future.set_result(i.payload)
        if sum(len(g) for g in groups) == 4:
            done.set()

    core = BatchingCore(dispatch=dispatch, max_batch=8, max_wait_s=0.2,
                        name="test_core", keyed=True)
    try:
        items = [WorkItem(n, key="a" if n % 2 == 0 else "b")
                 for n in range(4)]
        for item in items:
            core.put(item)
        assert done.wait(10)
        for item in items:
            assert item.future.result(timeout=5) == item.payload
        for g in groups:
            assert len(set(g)) == 1  # never a mixed-shape dispatch
    finally:
        core.shutdown()


def test_core_dispatch_error_fails_only_that_group():
    def dispatch(items):
        if items[0].key == "bad":
            raise RuntimeError("device on fire")
        for i in items:
            i.future.set_result("ok")

    core = BatchingCore(dispatch=dispatch, max_batch=8, max_wait_s=0.05,
                        name="test_core", keyed=True)
    try:
        bad = WorkItem(0, key="bad")
        core.put(bad)
        with pytest.raises(RuntimeError, match="on fire"):
            bad.future.result(timeout=10)
        good = WorkItem(1, key="good")
        core.put(good)
        assert good.future.result(timeout=10) == "ok"  # worker survived
    finally:
        core.shutdown()


def test_core_crash_containment_fails_queued_typed():
    """An exception escaping the gather loop itself (not the dispatch)
    fails gathered AND queued futures with SchedulerCrashed — the
    contract the scheduler owned alone before the core unification now
    covers every engine built on it."""
    crashed = []

    def dispatch(items):
        raise BaseExceptionGroupStub()  # never reached; key blows first

    class BaseExceptionGroupStub(Exception):
        pass

    core = BatchingCore(dispatch=dispatch, max_batch=4, max_wait_s=0.05,
                        name="test_core", drop_dead=True,
                        on_crash=lambda err, items: crashed.append(
                            (err, len(items))))

    class _BadDeadline:
        cancelled = False

        def alive(self):
            raise RuntimeError("deadline check exploded")

    item = WorkItem("x", deadline=_BadDeadline())
    core.put(item)
    with pytest.raises(SchedulerCrashed):
        item.future.result(timeout=10)
    assert crashed and crashed[0][1] >= 1
    core.shutdown()


def test_core_shutdown_fails_pending_futures():
    gate = threading.Event()

    def dispatch(items):
        gate.wait(10)
        raise RuntimeError("never mind")

    core = BatchingCore(dispatch=dispatch, max_batch=1, max_wait_s=0.0,
                        name="test_core",
                        closed_reason="engine closed in test")
    first = WorkItem("occupies the worker")
    core.put(first)
    time.sleep(0.05)
    queued = WorkItem("stuck in queue")
    core.put(queued)
    gate.set()
    core.shutdown()
    with pytest.raises(Exception):
        queued.future.result(timeout=5)
    with pytest.raises(Exception):
        first.future.result(timeout=5)


# ---------------------------------------------------------------------------
# IterationLoop (fake dispatch; no device)
# ---------------------------------------------------------------------------

def _echo_loop(batches, max_batch=8, **kwargs):
    """Loop whose dispatch records (n_rows, bucket) and echoes payloads."""

    def dispatch(key, payloads, b):
        batches.append((key, len(payloads), b))
        return list(payloads), {"frame_bucket": key}

    return IterationLoop(dispatch, max_batch=max_batch,
                         name="test_iter", **kwargs)


def test_iteration_join_submit_retire_roundtrip():
    batches = []
    loop = _echo_loop(batches)
    try:
        h = loop.join()
        futs = [loop.submit(h, 16, f"row{i}") for i in range(3)]
        assert [f.result(timeout=10) for f in futs] == \
            ["row0", "row1", "row2"]
        loop.retire(h)
        deadline = time.monotonic() + 5
        while loop.resident_streams and time.monotonic() < deadline:
            time.sleep(0.01)
        assert loop.resident_streams == 0
        assert loop.stats["joined"] == 1 and loop.stats["retired"] == 1
    finally:
        loop.close()


def test_iteration_graduated_bucket_padding():
    """Three concurrent rows pad to bucket 4, not the canonical max 8 —
    the padding-waste win iteration mode exists for.  Deterministic: the
    three rows queue while iteration 1 is blocked in flight, so they
    must share iteration 2."""
    batches = []
    in_flight = threading.Event()
    release = threading.Event()

    def dispatch(key, payloads, b):
        in_flight.set()
        release.wait(10)
        batches.append((len(payloads), b))
        return list(payloads), {}

    loop = IterationLoop(dispatch, max_batch=8, name="test_iter")
    try:
        warm = loop.join()
        f0 = loop.submit(warm, 16, "warm")
        assert in_flight.wait(10)  # iteration 1 pinned in flight
        handles = [loop.join() for _ in range(3)]
        futs = [loop.submit(h, 16, i) for i, h in enumerate(handles)]
        release.set()
        f0.result(timeout=10)
        for f in futs:
            f.result(timeout=10)
        assert (3, 4) in batches, batches
    finally:
        loop.close()


def test_iteration_join_mid_flight_at_boundary():
    """A stream joining while an iteration is in flight rides the NEXT
    iteration alongside the resident stream's rows."""
    batches = []
    in_flight = threading.Event()
    release = threading.Event()

    def dispatch(key, payloads, b):
        in_flight.set()
        release.wait(10)
        batches.append(sorted(payloads))
        return list(payloads), {}

    loop = IterationLoop(dispatch, max_batch=8, name="test_iter")
    try:
        a = loop.join()
        fa1 = loop.submit(a, 16, "a1")
        assert in_flight.wait(10)  # iteration 1 running with a1 alone
        b = loop.join()            # mid-flight join
        fa2 = loop.submit(a, 16, "a2")
        fb1 = loop.submit(b, 16, "b1")
        release.set()
        for f in (fa1, fa2, fb1):
            f.result(timeout=10)
        assert batches[0] == ["a1"]
        # the boundary admitted both: a2 and b1 share iteration 2
        assert ["a2", "b1"] in batches, batches
    finally:
        loop.close()


def test_iteration_deadline_expiry_fails_only_that_stream():
    batches = []
    loop = _echo_loop(batches)
    try:
        good = loop.join()
        doomed = loop.join(deadline=Deadline.after(0.01))
        time.sleep(0.05)  # let the deadline expire
        f_doomed = loop.submit(doomed, 16, "dead")
        f_good = loop.submit(good, 16, "alive")
        assert f_good.result(timeout=10) == "alive"
        with pytest.raises(DeadlineExceeded):
            f_doomed.result(timeout=10)
        assert loop.stats["expired"] == 1
    finally:
        loop.close()


def test_iteration_drain_retires_loop_at_boundary():
    batches = []
    loop = _echo_loop(batches)
    h = loop.join()
    fut = loop.submit(h, 16, "last row")
    loop.start_draining()
    # resident work finishes during the drain (in-flight streams keep
    # their riders); the loop exits at the boundary after the retire
    assert fut.result(timeout=10) == "last row"
    loop.retire(h)
    loop._thread.join(timeout=10)
    assert not loop._thread.is_alive()
    # new joins are refused typed while draining (a deploy, not a hang)
    with pytest.raises(OperationError, match="draining"):
        loop.join()
    loop.close()


def test_iteration_close_fails_pending_typed():
    gate = threading.Event()

    def dispatch(key, payloads, b):
        gate.wait(10)
        return list(payloads), {}

    loop = IterationLoop(dispatch, max_batch=8, name="test_iter")
    h = loop.join()
    first = loop.submit(h, 16, "in flight")
    time.sleep(0.05)
    pending = loop.submit(h, 32, "pending other width")
    gate.set()
    loop.close()
    for fut in (first, pending):
        try:
            fut.result(timeout=5)  # in-flight row may still resolve
        except Exception as e:
            assert isinstance(e, OperationError) or fut.cancelled()
    after = loop.submit(h, 16, "after close")
    with pytest.raises(OperationError, match="closed"):
        after.result(timeout=5)


def test_iteration_submit_close_race_fails_future():
    """Review-pass pin (the BatchingCore.put race, iteration edition):
    a submit whose put lands after close()'s inbox drain must still
    resolve its future typed, never leave the caller blocked forever."""
    loop = _echo_loop([])
    h = loop.join()
    real_put = loop._inbox.put
    armed = [True]

    def racing_put(entry):
        if armed[0] and entry is not None and entry[0] == "work":
            armed[0] = False
            loop.close()  # drain runs BEFORE the item lands
        return real_put(entry)

    loop._inbox.put = racing_put
    fut = loop.submit(h, 16, "raced")
    with pytest.raises(OperationError, match="closed"):
        fut.result(timeout=5)


def test_iteration_submit_after_drain_exit_fails_fast():
    """A drain-complete loop exit marks the loop closed: a late submit
    (or join) fails typed instead of queueing into a dead inbox."""
    loop = _echo_loop([])
    h = loop.join()
    loop.retire(h)
    loop.start_draining()
    loop._thread.join(timeout=10)
    assert not loop._thread.is_alive()
    fut = loop.submit(h, 16, "late")
    assert isinstance(fut.exception(timeout=5), OperationError)
    with pytest.raises(OperationError, match="draining"):
        loop.join()
    loop.close()


def test_iteration_dispatch_error_fails_rows_loop_survives():
    calls = []

    def dispatch(key, payloads, b):
        calls.append(key)
        if key == "boom":
            raise RuntimeError("iteration dispatch failed")
        return list(payloads), {}

    loop = IterationLoop(dispatch, max_batch=8, name="test_iter")
    try:
        h = loop.join()
        bad = loop.submit(h, "boom", "x")
        with pytest.raises(RuntimeError, match="iteration dispatch"):
            bad.result(timeout=10)
        good = loop.submit(h, "fine", "y")
        assert good.result(timeout=10) == "y"  # loop kept serving
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# pipelined iteration fetch (SONATA_ITER_PIPELINE, ISSUE 11)
# ---------------------------------------------------------------------------

def test_iter_pipeline_env_resolution():
    from sonata_tpu.synth.batching import resolve_iter_pipeline

    assert resolve_iter_pipeline(env={}) is True  # default: pipelined
    assert resolve_iter_pipeline(
        env={"SONATA_ITER_PIPELINE": "0"}) is False
    assert resolve_iter_pipeline(
        env={"SONATA_ITER_PIPELINE": "1"}) is True
    with pytest.raises(OperationError, match="SONATA_ITER_PIPELINE"):
        resolve_iter_pipeline(env={"SONATA_ITER_PIPELINE": "yes"})


def _two_phase_loop(*, pipeline, dispatched=None, finish_gate=None,
                    finish_fail=(), max_batch=8):
    """Loop whose dispatch phase records and returns a ticket; finish
    optionally blocks on ``finish_gate`` and fails tickets whose key is
    in ``finish_fail``."""
    dispatched = dispatched if dispatched is not None else []

    def dispatch(key, payloads, b):
        dispatched.append((key, len(payloads), b))
        return (key, list(payloads)), {"frame_bucket": key}

    def finish(ticket):
        key, payloads = ticket
        if finish_gate is not None:
            assert finish_gate.wait(10)
        if key in finish_fail:
            raise RuntimeError(f"fetch failed for {key}")
        return payloads

    return IterationLoop(dispatch, finish=finish, max_batch=max_batch,
                         name="test_iter_pipe", pipeline=pipeline,
                         idle_poll_s=0.05)


def test_pipelined_fetch_overlaps_next_dispatch():
    """THE pipelining contract: iteration k+1's dispatch is issued while
    k's fetch is still blocked in the finisher — observable as the
    second dispatch landing before the first finish completes, and as
    the loop's `fetch_overlapped` counter."""
    dispatched = []
    gate = threading.Event()
    loop = _two_phase_loop(pipeline=True, dispatched=dispatched,
                           finish_gate=gate)
    try:
        h = loop.join()
        f1 = loop.submit(h, "k", "row-k")
        # wait until iteration k is dispatched and parked in the fetch
        deadline = time.monotonic() + 5
        while len(dispatched) < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert dispatched == [("k", 1, 1)]
        f2 = loop.submit(h, "k+1", "row-k1")
        # k+1 must DISPATCH while k's fetch is still gated
        deadline = time.monotonic() + 5
        while len(dispatched) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(dispatched) == 2, "k+1 did not dispatch during k's fetch"
        assert not f1.done()  # k still fetching
        gate.set()
        assert f1.result(timeout=10) == "row-k"
        assert f2.result(timeout=10) == "row-k1"
        assert loop.stats["fetch_overlapped"] >= 1
    finally:
        gate.set()
        loop.close()


def test_sync_arm_never_overlaps():
    """SONATA_ITER_PIPELINE=0 (the bench A/B arm): same two-phase owner
    hooks, fetch inline on the worker — zero overlap by construction."""
    loop = _two_phase_loop(pipeline=False)
    try:
        h = loop.join()
        futs = [loop.submit(h, "w", i) for i in range(6)]
        assert [f.result(timeout=10) for f in futs] == list(range(6))
        assert loop.stats["fetch_overlapped"] == 0
        assert loop._finisher is None  # no fetch thread in the sync arm
    finally:
        loop.close()


def test_pipelined_fetch_error_fails_only_k_while_k1_resolves():
    """Failure surface: a fetch error in iteration k fails only k's
    rows; iteration k+1 — already dispatched behind it — still resolves
    with real results."""
    gate = threading.Event()
    dispatched = []
    loop = _two_phase_loop(pipeline=True, dispatched=dispatched,
                           finish_gate=gate, finish_fail={"bad"})
    try:
        h = loop.join()
        f_bad = loop.submit(h, "bad", "doomed")
        deadline = time.monotonic() + 5
        while len(dispatched) < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        f_good = loop.submit(h, "good", "fine")
        deadline = time.monotonic() + 5
        while len(dispatched) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert len(dispatched) == 2  # k+1 dispatched before k finished
        gate.set()
        with pytest.raises(RuntimeError, match="fetch failed"):
            f_bad.result(timeout=10)
        assert f_good.result(timeout=10) == "fine"
        # the loop survived the fetch error and keeps serving
        f_next = loop.submit(h, "good", "still serving")
        assert f_next.result(timeout=10) == "still serving"
    finally:
        gate.set()
        loop.close()


def test_pipelined_deadline_expiry_lands_at_finish_boundary():
    """A stream whose deadline expires while its row is IN FLIGHT: the
    dispatched row still resolves with its real result at the finish
    boundary; only rows still pending fail typed."""
    gate = threading.Event()
    dispatched = []
    loop = _two_phase_loop(pipeline=True, dispatched=dispatched,
                           finish_gate=gate, max_batch=1)
    try:
        h = loop.join(deadline=Deadline.after(0.15))
        f_inflight = loop.submit(h, "w", "made it")
        deadline = time.monotonic() + 5
        while len(dispatched) < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.3)  # stream deadline expires; fetch still gated
        # submitted AFTER expiry: admitted at the boundary, then the
        # expiry check fails it before it can dispatch
        f_pending = loop.submit(h, "w", "too late")
        gate.set()
        # the in-flight row keeps its finish boundary
        assert f_inflight.result(timeout=10) == "made it"
        with pytest.raises(DeadlineExceeded):
            f_pending.result(timeout=10)
        assert loop.stats["expired"] == 1
        assert loop.stats["retired"] == loop.stats["joined"] == 1
    finally:
        gate.set()
        loop.close()


def test_pipelined_drain_lands_at_finish_boundary():
    """Drain with a fetch in flight: the loop exits at the boundary and
    the in-flight iteration still resolves with its REAL result — drain
    must never turn a dispatched row into an error."""
    gate = threading.Event()
    dispatched = []
    loop = _two_phase_loop(pipeline=True, dispatched=dispatched,
                           finish_gate=gate)
    h = loop.join()
    fut = loop.submit(h, "w", "drained row")
    deadline = time.monotonic() + 5
    while len(dispatched) < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    loop.retire(h)
    loop.start_draining()
    assert not fut.done()  # still fetching across the drain
    gate.set()
    assert fut.result(timeout=10) == "drained row"
    loop._thread.join(timeout=10)
    assert not loop._thread.is_alive()
    loop._finisher.join(timeout=10)
    assert not loop._finisher.is_alive()
    with pytest.raises(OperationError, match="draining|closed"):
        loop.join()
    loop.close()


def test_finisher_crash_fails_both_inflight_iterations_typed():
    """Finisher-crash containment: with the fetch thread gone, BOTH
    in-flight iterations (mid-finish + dispatched-behind) fail typed
    SchedulerCrashed instead of stranding their consumers."""
    gate = threading.Event()
    dispatched = []
    loop = _two_phase_loop(pipeline=True, dispatched=dispatched)
    real_settle = loop._settle

    def crashing_settle(flight):
        assert gate.wait(10)  # hold until BOTH iterations are in flight
        raise RuntimeError("settle machinery exploded")

    loop._settle = crashing_settle
    try:
        h = loop.join()
        f1 = loop.submit(h, "a", "x")
        deadline = time.monotonic() + 5
        while len(dispatched) < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        f2 = loop.submit(h, "b", "y")
        deadline = time.monotonic() + 5
        while len(dispatched) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        gate.set()
        with pytest.raises(SchedulerCrashed):
            f1.result(timeout=10)
        with pytest.raises(SchedulerCrashed):
            f2.result(timeout=10)
        # containment closed the loop; late submits fail fast
        fut = loop.submit(h, "a", "late")
        assert isinstance(fut.exception(timeout=5), OperationError)
    finally:
        gate.set()
        loop._settle = real_settle
        loop.close()


def test_finisher_crash_racing_worker_put_fails_flight_typed():
    """Review-pass pin (the put-vs-crash-drain race): the finisher
    crashes and drains the fetch queue while the worker is still inside
    its dispatch — the worker's subsequent put lands in a queue nobody
    reads, so its post-put re-check must drain it typed, never leaving
    the consumer blocked forever in fut.result()."""
    crash_done = threading.Event()

    def dispatch(key, payloads, b):
        if key == "b":
            # hold iteration 2's dispatch open until the finisher's
            # crash containment has finished its (empty-queue) drain
            assert crash_done.wait(10)
        return (key, list(payloads)), {}

    loop = IterationLoop(dispatch, finish=lambda t: t[1], max_batch=8,
                         name="test_iter_race", pipeline=True,
                         idle_poll_s=0.05)
    orig_crashed = loop._finisher_crashed

    def crashed(exc, flight):
        orig_crashed(exc, flight)
        crash_done.set()

    loop._finisher_crashed = crashed
    loop._settle = lambda flight: (_ for _ in ()).throw(
        RuntimeError("settle machinery exploded"))
    try:
        h = loop.join()
        f1 = loop.submit(h, "a", "x")  # crashes the finisher
        f2 = loop.submit(h, "b", "y")  # put lands after the crash drain
        with pytest.raises(SchedulerCrashed):
            f1.result(timeout=10)
        with pytest.raises(SchedulerCrashed):
            f2.result(timeout=10)
    finally:
        crash_done.set()
        loop.close()


def test_worker_crash_fails_picked_rows_typed():
    """Worker-side containment: an infrastructure fault AFTER rows are
    picked (here: the pipeline-headroom acquire) fails those rows typed
    — never a consumer blocked forever in fut.result()."""
    loop = _two_phase_loop(pipeline=True)
    loop._acquire_slot = lambda: (_ for _ in ()).throw(
        RuntimeError("acquire exploded"))
    try:
        h = loop.join()
        fut = loop.submit(h, "w", "row")
        with pytest.raises(SchedulerCrashed):
            fut.result(timeout=10)
    finally:
        loop.close()


def test_pipelined_attribution_never_disagrees_across_threads():
    """The ISSUE-11 accounting fix, extending the PR-7 exactly-equal
    pin: padding attrs freeze at the DISPATCH phase (worker thread),
    and the finish phase (finisher thread) feeds the SAME dict to both
    the trace span and scope.note_dispatch — waste == span duration x
    the span's own padding_ratio, exactly, across the thread split."""
    from sonata_tpu.serving import scope as scope_mod
    from sonata_tpu.serving import tracing

    noted = []

    class _Scope:
        def note_dispatch(self, duration_s, attrs):
            noted.append((duration_s, attrs))

    sc = _Scope()
    scope_mod.install(sc)
    gate = threading.Event()
    loop = _two_phase_loop(pipeline=True, finish_gate=gate)
    tracer = tracing.Tracer(enabled=True, recent=8, slowest=4)
    try:
        trace = tracer.start_trace("req", request_id="pipe-pin")
        with tracing.use_trace(trace):
            h = loop.join()
            futs = [loop.submit(h, "w", i) for i in range(3)]
        gate.set()
        for f in futs:
            f.result(timeout=10)
        trace.finish("ok")
        spans = [s for s in trace.spans_snapshot() if s.name == "dispatch"]
        assert spans and noted
        span = spans[0]
        duration, attrs = noted[0]
        # one frozen dict feeds both surfaces (Span copies it): every
        # attribution field — padding included — is exactly equal
        assert span.attrs == attrs
        assert attrs["mode"] == "iteration"
        assert duration == pytest.approx(span.end - span.start)
        waste = duration * attrs["padding_ratio"]
        assert waste == (span.end - span.start) * span.attrs["padding_ratio"]
    finally:
        gate.set()
        scope_mod.uninstall(sc)
        loop.close()


# ---------------------------------------------------------------------------
# _pick_rows: head-timestamp k-way merge == the old sorted selection
# ---------------------------------------------------------------------------

def _old_pick_rows(streams, max_batch):
    """The pre-ISSUE-11 selection, verbatim (materialize + sort the full
    candidate list): the equivalence reference."""
    heads = [(s["pending"][0].t_submit, h)
             for h, s in streams.items() if s["pending"]]
    if not heads:
        return None, []
    _, oldest = min(heads)
    key = streams[oldest]["pending"][0].key
    rows = []
    candidates = sorted(
        ((item.t_submit, h, i, item)
         for h, s in streams.items()
         for i, item in enumerate(s["pending"]) if item.key == key))
    taken = {}
    for _t, h, _i, item in candidates:
        if len(rows) >= max_batch:
            break
        rows.append((h, item))
        taken.setdefault(h, []).append(item)
    for h, items in taken.items():
        s = streams[h]
        s["pending"] = [it for it in s["pending"] if it not in items]
    return key, rows


def test_pick_rows_equivalent_to_old_sorted_selection():
    """Randomized workloads (random slot counts, per-slot FIFO pending,
    mixed keys incl. ties): draining the loop's k-way-merge selection
    iteration by iteration picks EXACTLY the rows, in exactly the
    order, of the old sort-everything selection."""
    import random

    from sonata_tpu.synth.batching import StreamSlot

    rng = random.Random(1234)
    for trial in range(50):
        max_batch = rng.choice([1, 2, 4, 8])
        n_slots = rng.randint(1, 6)
        keys = [16, 32, 64]
        loop = IterationLoop(lambda *a: ([], {}), max_batch=max_batch,
                             name="test_pick", pipeline=False)
        loop.close()  # worker gone: _pick_rows drives the state directly
        t = 0.0
        mirror = {}
        for h in range(1, n_slots + 1):
            slot = StreamSlot(None, None)
            for _ in range(rng.randint(0, 7)):
                item = WorkItem(f"p{h}-{t}", key=rng.choice(keys))
                # controlled timestamps: FIFO-monotone per slot, with
                # occasional cross-slot ties
                t += rng.choice([0.0, 1.0, 2.0])
                item.t_submit = t
                slot.pending.append(item)
            loop._streams[h] = slot
            mirror[h] = {"pending": list(slot.pending)}
        # drain both selections to empty; sequences must match exactly
        while True:
            key_new, rows_new = loop._pick_rows()
            key_old, rows_old = _old_pick_rows(mirror, max_batch)
            assert key_new == key_old, trial
            assert [(h, it.payload) for h, it in rows_new] == \
                [(h, it.payload) for h, it in rows_old], trial
            if not rows_new:
                break


# ---------------------------------------------------------------------------
# piper integration: the real streaming path in iteration mode
# ---------------------------------------------------------------------------

@pytest.fixture
def iteration_env(monkeypatch):
    monkeypatch.setenv("SONATA_BATCH_MODE", "iteration")
    monkeypatch.setenv("SONATA_DISPATCH_POLICY", "on")


PHRASE = "tɛst nʌmbɚ wˈʌn tuː θɹˈiː"


def test_iteration_streams_share_iterations(iteration_env):
    v = tiny_voice(seed=31)
    try:
        results = [None] * 4
        # long utterance (many windows) so the four streams reliably
        # overlap in the loop even under hostile thread scheduling
        long_phrase = "ðɪs ɪz ə lˈɔːŋ ˈʌtɚɹəns wɪθ mˈɛni wˈɪndoʊz " * 3
        barrier = threading.Barrier(4, timeout=10)

        def run(i):
            barrier.wait()
            chunks = list(v.stream_synthesis(long_phrase, 8, 2))
            results[i] = np.concatenate([c.samples.data for c in chunks])

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r is not None and len(r) > 0 for r in results)
        stats = v.dispatch_stats()
        assert stats["batch_mode"] == "iteration"
        # a consumer's retire is a message the loop thread processes on
        # its next gather, so "retired" can lag the joins briefly —
        # poll for the book balance instead of reading it once
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            it = v.dispatch_stats()["iteration"]
            if it["retired"] == 4:
                break
            time.sleep(0.05)
        assert it["joined"] == 4 and it["retired"] == 4
        assert it["dispatches"] < it["requests"]  # rows shared iterations
        # graduated ladder: padding stays below the canonical-max rule's
        # (which pads EVERY multi-stream wave to 8 rows)
        assert it["padded_rows"] < it["rows"]
    finally:
        v.close()


def test_iteration_join_retire_without_recompile(iteration_env):
    """THE recompile-free property: after prewarm (which warms the
    graduated ladder in iteration mode), a staggered join/retire
    sequence grows no executable cache — mid-occupancy iterations land
    on lattice-warmed shapes."""
    v = tiny_voice(seed=32)
    try:
        v.prewarm(streaming=True, chunk_size=12, chunk_padding=2)

        def cache_keys():
            def sizes(d):
                return {k: getattr(fn, "_cache_size", lambda: -1)()
                        for k, fn in d.items()}

            return (sizes(v._dec_cache), sizes(v._enc_cache),
                    sizes(v._aco_cache))

        warmed = cache_keys()
        phrase = list(v.phonemize_text(v._PREWARM_TEXTS[0]))[0]
        started = threading.Event()
        results = [None] * 2

        def run_a():
            gen = v.stream_synthesis(phrase, 12, 2)
            chunks = [next(gen)]
            started.set()  # A mid-flight...
            chunks.extend(gen)
            results[0] = chunks

        def run_b():
            started.wait(10)  # ...when B joins
            results[1] = list(v.stream_synthesis(phrase, 12, 2))

        ta, tb = threading.Thread(target=run_a), \
            threading.Thread(target=run_b)
        ta.start(), tb.start()
        ta.join(), tb.join()
        assert all(r for r in results)
        assert cache_keys() == warmed, "join/retire caused a recompile"
    finally:
        v.close()


def test_iteration_dispatch_spans_in_trace(iteration_env):
    """Every iteration records ONE shared dispatch span (mode=iteration,
    peers, padding) into each rider's trace — the PR-4 attribution
    contract carried to the persistent loop."""
    from sonata_tpu.serving import tracing
    from sonata_tpu.synth import SpeechSynthesizer

    v = tiny_voice(seed=38)
    try:
        synth = SpeechSynthesizer(v)
        tracer = tracing.Tracer(enabled=True, recent=8, slowest=4)
        with tracer.trace_request("iter-span-pin"):
            for _c in synth.synthesize_streamed(
                    "A sentence for span checking purposes.",
                    chunk_size=12, chunk_padding=2):
                pass
        doc = tracer.recent_traces()[0].to_dict()
        dspans = [s for s in doc["spans"] if s["name"] == "dispatch"
                  and s.get("attrs", {}).get("mode") == "iteration"]
        assert dspans, [s["name"] for s in doc["spans"]]
        for s in dspans:
            attrs = s["attrs"]
            assert {"batch_bucket", "padding_ratio", "request_ids",
                    "dispatch_id", "frame_bucket", "compile"} \
                <= set(attrs)
            assert doc["request_id"] in attrs["request_ids"]
    finally:
        v.close()


def test_iteration_stream_deadline_fails_alone(iteration_env):
    """A stream whose deadline expires mid-flight fails typed while a
    concurrent batch peer completes with full audio."""
    v = tiny_voice(seed=33)
    try:
        errors, audio = [], []
        barrier = threading.Barrier(2, timeout=10)

        def run_doomed():
            barrier.wait()
            try:
                gen = v.stream_synthesis(PHRASE, 12, 2,
                                         deadline=Deadline.after(0.001))
                time.sleep(0.05)
                list(gen)
            except Exception as e:
                errors.append(e)

        def run_good():
            barrier.wait()
            audio.extend(v.stream_synthesis(PHRASE, 12, 2))

        td = threading.Thread(target=run_doomed)
        tg = threading.Thread(target=run_good)
        td.start(), tg.start()
        td.join(), tg.join()
        assert audio and all(len(a.samples) > 0 for a in audio)
        assert errors and isinstance(errors[0],
                                     (DeadlineExceeded, OperationError))
    finally:
        v.close()


def test_ladder_forces_new_streams_to_dispatch_mode(iteration_env):
    """Level >= 1 routes NEW streams to the wave coalescer; recovery
    re-admits the iteration loop — per stream, no restart."""
    from sonata_tpu.synth.stream_engines import (
        _IterationStreamDecoder,
        _StreamDecodeCoalescer,
    )

    class _Ladder:
        level = 0

        def current_level(self):
            return self.level

    ladder = _Ladder()
    degradation_mod.install(ladder)
    v = tiny_voice(seed=34)
    try:
        assert isinstance(v._stream_decoder, _IterationStreamDecoder)
        ladder.level = 1
        assert isinstance(v._stream_decoder, _StreamDecodeCoalescer)
        ladder.level = 0
        assert isinstance(v._stream_decoder, _IterationStreamDecoder)
    finally:
        degradation_mod.uninstall(ladder)
        v.close()


def test_voice_start_draining_refuses_new_streams(iteration_env):
    """The serving drain path (grpc_server calls
    ``voice.start_draining`` alongside the pool's): NEW iteration-mode
    streams refuse typed while a resident stream finishes with full
    audio."""
    v = tiny_voice(seed=39)
    try:
        gen = v.stream_synthesis(PHRASE, 12, 2)
        chunks = [next(gen)]       # resident mid-flight
        v.start_draining()
        with pytest.raises(OperationError, match="draining"):
            list(v.stream_synthesis(PHRASE, 12, 2))  # new join refused
        chunks.extend(gen)         # the resident stream still finishes
        assert all(len(c.samples) > 0 for c in chunks)
        # the retire lands at the loop's next iteration boundary
        deadline = time.monotonic() + 5
        stats = v.dispatch_stats()["iteration"]
        while (stats["retired"] != stats["joined"]
               and time.monotonic() < deadline):
            time.sleep(0.02)
            stats = v.dispatch_stats()["iteration"]
        assert stats["retired"] == stats["joined"] == 1
    finally:
        v.close()


def test_voice_close_fails_iteration_submits(iteration_env):
    import jax.numpy as jnp

    v = tiny_voice(seed=35)
    list(v.stream_synthesis(PHRASE, 12, 2))  # materialize the loop
    decoder = v._iter_decoder
    assert decoder is not None
    v.close()
    z = jnp.zeros((16, v.hp.inter_channels), dtype=jnp.float32)
    fut = decoder.submit(z, 0, 8, None)
    assert isinstance(fut.exception(timeout=5), OperationError)
    # terminal: the slot stays None, no thread respawn
    assert v._iter_decoder is None


def test_lattice_grows_iteration_shapes(iteration_env):
    v = tiny_voice(seed=36)
    try:
        full = v.lattice_shapes("full")
        minimal = v.lattice_shapes("minimal")
        wdec_full = [s for s in full if s[0] == "wdec"]
        wdec_min = [s for s in minimal if s[0] == "wdec"]
        assert wdec_full, "iteration mode must grow the lattice"
        # full warms the whole graduated ladder; minimal batch 1 only
        assert {s[2] for s in wdec_full} == {1, 2, 4, 8}
        assert {s[2] for s in wdec_min} == {1}
        assert set(wdec_min) <= set(wdec_full)
        # warm_shape understands the tagged tuples: the executable lands
        # in the decode cache real iterations dispatch through — the
        # FUSED program when the epilogue arm is on (the default), via
        # the same _wdec_cache_key live dispatches resolve
        shape = wdec_full[0]
        v.warm_shape(shape)
        _tag, width, b, has_sid = shape
        assert v._wdec_cache_key(width, b, has_sid) in v._dec_cache
    finally:
        v.close()


def test_lattice_has_no_iteration_shapes_in_dispatch_mode(monkeypatch):
    monkeypatch.setenv("SONATA_BATCH_MODE", "dispatch")
    monkeypatch.setenv("SONATA_DISPATCH_POLICY", "on")
    v = tiny_voice(seed=37)
    try:
        assert all(s[0] != "wdec" for s in v.lattice_shapes("full"))
    finally:
        v.close()


# ---------------------------------------------------------------------------
# pool composition: breaker trips stay exactly-once under iteration mode
# ---------------------------------------------------------------------------

def test_pool_resubmits_exactly_once_under_iteration_mode(monkeypatch):
    """The pool's breaker/resubmission machinery is batch-mode-agnostic:
    with SONATA_BATCH_MODE=iteration armed process-wide, a replica
    fault still resubmits the affected request exactly once to a
    healthy replica and the client gets audio."""
    monkeypatch.setenv("SONATA_BATCH_MODE", "iteration")
    from sonata_tpu.serving.replicas import ReplicaPool
    from sonata_tpu.testing import FakeModel

    class FlakyModel(FakeModel):
        def __init__(self):
            super().__init__()
            self.fail = False

        def speak_batch(self, *args, **kwargs):
            if self.fail:
                raise RuntimeError("injected dispatch failure")
            return super().speak_batch(*args, **kwargs)

    flaky, healthy = FlakyModel(), FakeModel()
    pool = ReplicaPool([flaky, healthy],
                       scheduler_kwargs={"max_batch": 1,
                                         "max_wait_ms": 0.0},
                       breaker_threshold=1, probe_interval_s=60)
    try:
        flaky.fail = True
        # route until the flaky replica takes one (least-outstanding
        # alternates; a couple of submits guarantees a hit)
        audios = [pool.speak(f"sentence {i}", timeout=30)
                  for i in range(4)]
        assert all(len(a.samples) > 0 for a in audios)
        assert pool.stats["resubmitted"] == 1  # exactly once
        assert pool.stats["failed"] == 0       # the client never saw it
    finally:
        pool.shutdown()
