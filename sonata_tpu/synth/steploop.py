"""Step-wise generation: one loop per voice over a fixed set of slots.

:class:`~sonata_tpu.synth.batching.IterationLoop` batches independent
window decodes and keeps nothing between iterations.  A unit voice is the
other case: a row (one sentence) lives for hundreds of launches of one step
program, no launch yields audio, and what carries a row from launch to
launch (keys and values, convolution columns, a block half denoised) stays
on the device in the slot the row was given.  The loop, on a thread of its
own:

1. **admit**: a waiting row takes a free slot.  Where the engine's step
   can carry an arrival (``engine.carries(n_ids)``), the row's prompt rides
   the launch it arrives beside (``engine.step_admit``): **one prompt a
   launch**, the others wait for the launches that follow, which run back
   to back while anything waits; the row is live from the next launch on.
   Where it cannot (the engine offers no such step, or not for this
   prompt), the row is prefilled apart, one program a row between two
   steps, as many rows as have come;
2. **step**: one program over all ``S`` slots, a static shape, empty slots
   masked and counted;
3. **retire**: a row leaves after its last launch; its units go to the
   vocoder program (enqueued, not awaited: a finisher thread fetches the
   audio and resolves the row's future), its slot is free for the next
   admit.

**A launch is not a unit.**  What a launch leaves a row with is the
engine's to say, once, when the row joins (``engine.plan(ids, budget)``: the
launches the row lives, the units it holds after each, the positions each
attends over, which launches finish a block): one unit a launch for a
backbone that decodes token by token, a block of units every few launches
and nothing between for one that denoises blocks.  So the host still reads
nothing back to decide a launch: liveness is counted, not fetched.  The
loop keeps one step queued behind the running one and waits for the step
before (its expert load, a few numbers), which bounds the run-ahead and
times the steps.

**Slots** (:class:`SlotTable`) are the state manager's host half: which
slot holds which row.  The device half, the arrays two kinds of state live
in, belongs to the engine (``new_cache``).

**Tracing.**  A step serves every live row, so its span belongs to no one
request: the loop records its steps on a trace it owns (``ar-steps``,
closed every few seconds), one ``dispatch`` span per group of
:data:`STEP_GROUP` steps with ``kind: step`` (``docs/DEPLOY.md``, "Unit
voices", says what each attribute means).  The loop's own: ``steps``,
``slots``, the sums of :data:`ROW_SUMS` (``live_slot_steps`` counts the row
whose prompt a launch carried too), ``admit_steps``, ``prompt_tokens`` and
``arrivals``; per expert layer ``assignments``, ``experts_touched``,
``max_expert_assignments``, ``held_assignments`` and
``held_experts_touched``, and ``held_overflow_steps``; of its turns
``host_ms`` by phase, ``wall_ms`` and, beside ``host_ms``'s three,
``device_wait_ms`` (blocked until the step before had run), ``record_ms``
(the rest of settling it) and ``other_ms`` (the turn less its phases), which
sum to ``wall_ms``; of the longest turn ``turn_ms_max``, ``turn_max_phase``
and ``turn_max_step``; ``compile_ms`` and ``compiled`` where a program
compiled on the loop's thread outside a prefill's or a vocoder's launch; and
the engine's ``block_length``, ``denoising_steps``, ``expert_matmul`` and
``attention``.  **What the slots' cache is, the loop does not know**: the
engine's ``description``
(:class:`~sonata_tpu.models.unit_backbone.Description`) brings the
attributes a group's span carries as they are (``static``), the sums a
launch adds row by row beyond the loop's own (``row_sums``, and for a row
that attends over ``attended`` positions ``rows[attended]``: one look-up a
row), what a closed group derives from its sums (``closed(group)``), the
bytes the slots hold while the loop lives, by the series that exports them
(``resident``), and what a prefill span says beyond its shape
(``prefill(text_bucket)``, which the engine puts into ``shape``).  Each row
admitted (``admit``: ``step``, with the ``step_no`` that carried it, or
``apart``; ``blocks`` of the prompt kept whole, ``tail_ids`` left to the
first generated block) and each vocoder launch is a ``dispatch`` span
(``kind: prefill`` | ``vocode``) in the trace of the request the row
belongs to; both end when what their program produced is on the host, and
a vocoder's says what the finisher thread spent on it (``fetch_wait_ms``
until the program had run, ``finish_ms`` of its own work after).  A
prefill's and a vocoder's ``compile`` (``cold`` | ``cached``) is the
engine's to say (``shape``); a compile after the warm-up counts against
the voice (``scope.note_runtime_compile``).  The always-on counters are
:class:`~sonata_tpu.serving.tracing.StepStats`.  While ``/debug/profile``
holds a capture the loop's phases are mirrored into it as ``sonata:admit |
launch | retire | settle`` with ``step_no``.

The engine (a voice: :class:`~sonata_tpu.models.unit_voice.UnitVoice`)
gives ``slots``, ``expert_layers``, ``block_length``, ``denoising_steps``,
``expert_matmul``, ``attention``, ``description``, ``new_cache()``,
``plan(n_ids, budget)``, ``prefill(cache, slot, ids, temperature)``,
``step(cache, live, temperature, step_no)``, where its step carries
arrivals ``carries(n_ids)`` and ``step_admit(cache, live, temperature,
step_no, slot, ids, row_temperature)``, ``vocode(cache, slot, n_ids,
units)``, ``wait_audio(out)``, ``fetch_audio(out, units)`` and, for flagged
rows, ``dumped(plan, done)`` (which launches a row keeps),
``row_record(cache, slot)``, ``take_rows(kept, rows)`` and ``dump(ids,
budget, kept, record)``.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from concurrent.futures import Future
from pathlib import Path
from typing import Optional

import numpy as np

from ..core import OperationError
from ..serving import scope, tracing
from ..utils import profiling

log = logging.getLogger("sonata.steploop")

#: steps summed into one ``dispatch`` span of the loop's own trace
STEP_GROUP = 32
#: the loop's trace is finished (and a new one begun) this often
TRACE_SECONDS = 4.0
#: rows whose launch one gather program takes
DUMP_ROWS = 8

#: what a launch adds to its group's sums, row by row (the engine's
#: description brings the rest)
ROW_SUMS = ("live_slot_steps", "units", "positions", "denoise_row_passes",
            "commit_row_passes", "kv_positions")

DUMP_DIR_ENV = "SONATA_AR_DUMP_DIR"
DUMP_PREFIX_ENV = "SONATA_AR_DUMP_RID_PREFIX"


class SlotTable:
    """Which slot holds which row (lowest free slot first)."""

    def __init__(self, slots: int):
        self.rows: list = [None] * slots

    def take(self, row) -> Optional[int]:
        for slot, held in enumerate(self.rows):
            if held is None:
                self.rows[slot] = row
                return slot
        return None

    def release(self, slot: int) -> None:
        self.rows[slot] = None

    def live(self) -> list:
        return [row for row in self.rows if row is not None]

    @property
    def in_use(self) -> int:
        return sum(row is not None for row in self.rows)


class Row:
    """One sentence in flight: ``ids`` the prompt, ``budget`` the units it
    is given (its frames), ``temperature`` its sampling, ``plan`` what the
    engine said it will take, ``done`` its launches so far."""

    def __init__(self, ids: list, budget: int, temperature: float, plan):
        self.ids = ids
        self.budget = int(budget)
        self.temperature = float(temperature)
        self.plan = plan
        self.done = 0
        self.future: Future = Future()
        self.context = tracing.current()
        self.request_id = (self.context[0].request_id
                           if self.context else None)
        self.t_submit = time.monotonic()
        self.slot: Optional[int] = None
        #: flagged rows only: ``[launch, (gathered arrays, row of the
        #: gather)]`` (-1: the prefill's, row ``None``: the arrays are the
        #: row's own); the loop moves the arrays to the host as it goes (one
        #: store of the pair: the finisher may be reading), so that what is
        #: kept does not fill the device
        self.dump: Optional[list] = None

    def traced(self):
        """The row's request made current on the calling thread (the
        loop's), so that what compiles under the row's launch is a
        ``compile`` span of the request that waited for it."""
        return tracing.use_trace(*(self.context or (None,)))

    def span(self, start: float, end: float, **attrs) -> None:
        if self.context is not None:
            trace, parent = self.context
            trace.new_span("dispatch", parent=parent, start=start, end=end,
                           attrs=attrs)


class StepLoop:
    def __init__(self, engine, *, name: str = ""):
        self.engine = engine
        self.name = name
        self.slots = SlotTable(engine.slots)
        self.stats = tracing.step_stats()
        #: what the slots' cache is: the engine's to say
        self.description = engine.description
        self._row_sums = ROW_SUMS + self.description.row_sums
        self._resident = self.description.resident
        self.stats.record_resident(self._resident)
        self.layers = list(engine.expert_layers)
        dump_dir = os.environ.get(DUMP_DIR_ENV)
        self._dump_dir = Path(dump_dir) if dump_dir else None
        self._dump_prefix = os.environ.get(DUMP_PREFIX_ENV, "")
        self._cond = threading.Condition()
        self._waiting: collections.deque = collections.deque()
        self._closed = False
        self._draining = False
        self._finish: collections.deque = collections.deque()
        self._finish_cond = threading.Condition()
        self._step_no = 0
        self._trace = None
        self._trace_began = 0.0
        self._trace_seq = 0
        self._group = None
        #: what compiled on the loop's thread and no launch has claimed
        self._paid: list = []
        #: prefills enqueued whose load has not been read yet (read with
        #: the next step's, so that an admit does not wait for the device):
        #: ``(row, when admitted, the span's attributes, load)``
        self._admitted: list = []
        #: whether a row's prompt rides a step (None: the engine offers none)
        self._carries = getattr(engine, "carries", None)
        self._thread = threading.Thread(
            target=self._run, name=f"sonata_steploop_{name}", daemon=True)
        self._finisher = threading.Thread(
            target=self._run_finisher, name=f"sonata_stepfin_{name}",
            daemon=True)
        self._thread.start()
        self._finisher.start()

    # -- callers -------------------------------------------------------------
    def submit(self, ids: list, budget: int, temperature: float) -> Future:
        """A row joins the queue; its future resolves to what the engine's
        ``fetch_audio`` gives once its units have been through the
        vocoder."""
        row = Row(ids, budget, temperature,
                  self.engine.plan(len(ids), budget))
        if self._dump_dir is not None and row.request_id and \
                row.request_id.startswith(self._dump_prefix):
            row.dump = []
        with self._cond:
            if self._closed or self._draining:
                raise OperationError("the voice's step loop is closed")
            self._waiting.append(row)
            self._cond.notify()
        return row.future

    def start_draining(self) -> None:
        """Refuse new rows; those in flight finish."""
        with self._cond:
            self._draining = True

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            resident, self._resident = self._resident, {}
        self.stats.record_resident(resident, let_go=True)
        self._thread.join(timeout=30.0)
        with self._finish_cond:
            self._finish_cond.notify_all()
        self._finisher.join(timeout=30.0)

    # -- the loop ------------------------------------------------------------
    def _run(self) -> None:
        failure = "the voice's step loop was closed"
        try:
            # what compiles on this thread outside a prefill's or a
            # vocoder's launch (the step program, a gather, an eager
            # operation) is the step group's to report
            with tracing.compile_sink() as self._paid:
                self._loop()
        except Exception as e:  # the loop must fail its rows, not hang them
            log.exception("step loop %s failed", self.name)
            failure = f"step loop failed: {type(e).__name__}: {e}"
        with self._cond:
            self._closed = True
            rows = list(self._waiting) + self.slots.live()
            self._waiting.clear()
        for row in rows:
            if not row.future.done():
                row.future.set_exception(OperationError(failure))

    def _loop(self) -> None:
        engine = self.engine
        cache = engine.new_cache()
        pending = None          # the step before: (its load, when launched)
        turn_began = time.perf_counter()
        while True:
            with self._cond:
                while not self._closed and not self._waiting \
                        and not self.slots.in_use:
                    if pending is not None or self._group is not None:
                        break
                    self._cond.wait()
                    turn_began = time.perf_counter()    # idle is no turn
                if self._closed:
                    return
                # rows prefilled apart, and the one this launch carries
                arrivals, carried = [], None
                while self._waiting and self.slots.in_use + len(arrivals) \
                        + (carried is not None) < engine.slots:
                    if self._carries is None or not self._carries(
                            len(self._waiting[0].ids)):
                        arrivals.append(self._waiting.popleft())
                    elif carried is None:
                        carried = self._waiting.popleft()
                    else:
                        break       # one prompt a launch
            step_no = self._step_no
            t0 = time.perf_counter()
            if arrivals:
                with profiling.annotation("sonata:admit", step_no=step_no):
                    for row in arrivals:
                        cache, _ = self._admit(cache, row)
            t1 = time.perf_counter()
            rows = self.slots.live()
            if not rows and carried is None:
                # nothing to step: what is still in flight is waited for,
                # and the steps recorded so far go out
                self._settle(pending)
                pending = None
                self._close_group(time.monotonic())
                self._roll_trace(force=True)
                turn_began = time.perf_counter()
                continue
            live = np.zeros((engine.slots,), bool)
            temperature = np.zeros((engine.slots,), np.float32)
            sums = dict.fromkeys(self._row_sums, 0)
            described, looked = self.description.rows, []
            for row in rows:
                live[row.slot] = True
                temperature[row.slot] = row.temperature
                commits = row.plan.commits(row.done)
                sums["live_slot_steps"] += 1
                sums["positions"] += row.plan.block
                attended = row.plan.attended(row.done)
                sums["kv_positions"] += attended
                looked.append(described[attended])
                sums["commit_row_passes"] += commits
                sums["denoise_row_passes"] += not commits
                sums["units"] += row.plan.units(row.done + 1) \
                    - row.plan.units(row.done)
            sums.update(zip(self.description.row_sums,
                            map(sum, zip(*looked))))
            # the slot of the row this launch carries holds a row too
            sums["live_slot_steps"] += carried is not None
            launched = time.monotonic()
            joined = None
            with profiling.annotation("sonata:launch", step_no=step_no):
                if carried is None:
                    cache, kept, load = engine.step(cache, live, temperature,
                                                    step_no)
                else:
                    # the row is live from the next launch on
                    cache, (kept, load, joined) = self._admit(
                        cache, carried, (live, temperature, step_no))
                load.copy_to_host_async()
            self._step_no += 1
            t2 = time.perf_counter()
            with profiling.annotation("sonata:retire", step_no=step_no):
                due = [row for row in rows if row.dump is not None
                       and engine.dumped(row.plan, row.done)]
                gathers = []
                for k in range(0, len(due), DUMP_ROWS):
                    part = due[k:k + DUMP_ROWS]
                    got = engine.take_rows(kept, [r.slot for r in part]
                                           + [0] * (DUMP_ROWS - len(part)))
                    for a in got:
                        a.copy_to_host_async()
                    for j, row in enumerate(part):
                        row.dump.append([row.done, (got, j)])
                        gathers.append(row.dump[-1])
                for row in rows:
                    row.done += 1
                    if row.done >= row.plan.launches:
                        self._retire(cache, row)
            t3 = time.perf_counter()
            with profiling.annotation("sonata:settle", step_no=step_no):
                waited = self._settle(pending)
            t4 = time.perf_counter()
            # this turn, phase by phase; it joins a group's sums when its
            # own step is settled, a turn from now
            turn = {"admit": t1 - t0, "launch": t2 - t1, "retire": t3 - t2,
                    "device_wait": waited, "record": t4 - t3 - waited}
            wall = t4 - turn_began
            turn["other"] = wall - sum(turn.values())
            turn_began = t4
            self.stats.turns.observe(wall)
            pending = (load, launched, sums, gathers, joined,
                       (turn, wall, step_no,
                        len(arrivals) + (carried is not None)))

    def _admit(self, cache, row: Row, step: Optional[tuple] = None):
        """``row`` takes a slot and its prompt a launch: a prefill program
        of its own or, with ``step`` (``live, temperature, step_no``), the
        step itself.  Returns the cache and, of a step, what it gave
        (``kept``, ``load``) and what the row's ``prefill`` span waits
        with until that load is read (``row, when admitted, the span's
        attributes``); a prefill apart queues the like itself, with a load
        of its own."""
        engine = self.engine
        start = time.monotonic()
        slot = self.slots.take(row)
        row.slot = slot
        out, how = None, {"admit": "apart"}     # of the step, if any
        with row.traced():
            if step is None:
                cache, kept, load, shape = engine.prefill(
                    cache, slot, row.ids, row.temperature)
                load.copy_to_host_async()
            else:
                cache, *out, kept, shape = engine.step_admit(
                    cache, *step, slot, row.ids, row.temperature)
                how = {"admit": "step", "step_no": step[2]}
        if row.dump is not None and kept is not None:
            for a in kept:
                a.copy_to_host_async()
            row.dump.append([-1, (kept, None)])
        self._note_compile(shape, "prefill")
        block = engine.block_length
        joined = (row, start, dict(
            shape, kind="prefill", rows=1, tokens=len(row.ids), slot=slot,
            blocks=len(row.ids) // block, tail_ids=len(row.ids) % block,
            wait_ms=round((start - row.t_submit) * 1e3, 3), **how))
        if step is None:
            self._admitted.append((*joined, load))
        else:
            out.append(joined)
        self.stats.slots_in_use = self.slots.in_use
        if row.plan.launches <= 0:
            self._retire(cache, row)
        return cache, out

    def _retire(self, cache, row: Row) -> None:
        engine = self.engine
        start = time.monotonic()
        with row.traced():
            out, shape = engine.vocode(cache, row.slot, len(row.ids),
                                       row.budget)
        self._note_compile(shape, "vocode")
        record = None
        if row.dump is not None:
            record = engine.row_record(cache, row.slot)
        self.slots.release(row.slot)
        self.stats.record_retired()
        self.stats.slots_in_use = self.slots.in_use
        with self._finish_cond:
            self._finish.append((row, out, shape, record, start))
            self._finish_cond.notify()

    def _note_compile(self, shape: dict, kind: str) -> None:
        """A launch that compiled after the warm-up counts against the
        voice (``sonata_runtime_cold_compiles_total``)."""
        if shape.get("compile") == "cold":
            scope.note_runtime_compile(
                self.name or None,
                f"{kind} {', '.join(shape.get('compiled', ()))}")

    def _settle(self, pending) -> float:
        """The step before the one just launched has finished: fetch its
        load (this is where the loop waits for the device) and add it to
        the group's sums.  Returns the seconds spent blocked on the device
        (inside ``np.asarray(load)``, of the prefills enqueued this turn
        and of the step, and nowhere else)."""
        waited = 0.0
        admitted, self._admitted = self._admitted, []
        for row, start, attrs, load in admitted:
            t = time.perf_counter()
            loads = np.asarray(load)
            waited += time.perf_counter() - t
            if tracing.held_overflow(loads):
                attrs["held_overflow"] = True
            self.stats.record_prefill(attrs["tokens"], self.layers,
                                      loads, row.plan.units(0),
                                      attrs["expert_matmul"],
                                      attrs["attention"], voice=self.name)
            row.span(start, time.monotonic(), **attrs)
        if pending is None:
            return waited
        load, launched, sums, gathers, joined, \
            (turn, wall, step_no, arrivals) = pending
        t = time.perf_counter()
        loads = np.asarray(load)
        waited += time.perf_counter() - t
        now = time.monotonic()
        if joined is not None:
            # the step carried this row's prompt: its load is the step's
            row, start, attrs = joined
            self.stats.record_admit_step(attrs["tokens"], row.plan.units(0),
                                         voice=self.name)
            row.span(start, now, **attrs)
        for entry in gathers:
            # gathered behind a step that has finished: to the host, so that
            # a flagged row's logits do not pile up on the device
            arrays, j = entry[1]
            entry[1] = ([np.asarray(a)[j] for a in arrays], None)
        g = self._group
        if g is None:
            g = self._group = {
                "start": launched, "steps": 0,
                **dict.fromkeys(self._row_sums, 0),
                "assignments": [0] * len(self.layers),
                "experts_touched": [0] * len(self.layers),
                "max_expert_assignments": [0] * len(self.layers),
                "held_assignments": [0] * len(self.layers),
                "held_experts_touched": [0] * len(self.layers),
                "host_ms": dict.fromkeys(tracing.AR_HOST_PHASES, 0.0),
                "wall_ms": 0.0, "turn_ms_max": 0.0, "turn_max_phase": None,
                "turn_max_step": None, "arrivals": 0, "admit_steps": 0,
                "prompt_tokens": 0, "held_overflow_steps": 0,
                **{p + "_ms": 0.0 for p in tracing.AR_SETTLE_PHASES}}
        g["steps"] += 1
        g["held_overflow_steps"] += tracing.held_overflow(loads)
        if joined is not None:
            g["admit_steps"] += 1
            g["prompt_tokens"] += joined[2]["tokens"]
        for key, value in sums.items():
            g[key] += int(value)
        for k in range(len(self.layers)):
            g["experts_touched"][k] += int(loads[k][0])
            g["max_expert_assignments"][k] += int(loads[k][1])
            g["assignments"][k] += int(loads[k][2])
            touched, assigned = tracing.held_load(loads[k])
            g["held_experts_touched"][k] += int(touched)
            g["held_assignments"][k] += int(assigned)
        for phase, seconds in turn.items():
            if phase in g["host_ms"]:
                g["host_ms"][phase] += seconds * 1e3
            else:
                g[phase + "_ms"] += seconds * 1e3
        g["wall_ms"] += wall * 1e3
        g["arrivals"] += arrivals
        if wall * 1e3 > g["turn_ms_max"]:
            g.update(turn_ms_max=wall * 1e3, turn_max_step=step_no,
                     turn_max_phase=max(turn, key=turn.get))
        if g["steps"] >= STEP_GROUP:
            self._close_group(now)
            self._roll_trace()
        return waited

    def _close_group(self, end: float) -> None:
        g, self._group = self._group, None
        if g is None:
            return
        start = g.pop("start")
        g["host_ms"] = {k: round(v, 3) for k, v in g["host_ms"].items()}
        for key in ("wall_ms", "turn_ms_max", *(
                p + "_ms" for p in tracing.AR_SETTLE_PHASES)):
            g[key] = round(g[key], 3)
        took = tracing.launch_compile(self._paid)
        self._note_compile(took, "step group")
        del took["compile"]     # a group says what compiled, if anything
        g.update(took)
        g.update(kind="step", slots=self.engine.slots, layers=self.layers,
                 block_length=self.engine.block_length,
                 denoising_steps=self.engine.denoising_steps,
                 expert_matmul=self.engine.expert_matmul,
                 attention=self.engine.attention,
                 **self.description.static, **self.description.closed(g))
        self.stats.record_steps(g)
        if self._trace is None:
            self._trace = tracing.default_tracer().start_trace(
                "ar-steps", request_id=f"ar-steps-{self.name}-"
                                       f"{self._trace_seq}",
                voice=self.name)
            self._trace_began = start
            self._trace_seq += 1
        if self._trace is not None:
            self._trace.new_span("dispatch", start=start, end=end, attrs=g)

    def _roll_trace(self, force: bool = False) -> None:
        if self._trace is not None and (
                force or time.monotonic() - self._trace_began
                > TRACE_SECONDS):
            self._trace.finish("ok")
            self._trace = None

    # -- the finisher ----------------------------------------------------------
    def _run_finisher(self) -> None:
        while True:
            with self._finish_cond:
                while not self._finish and not self._closed:
                    self._finish_cond.wait(0.5)
                if not self._finish:
                    return
                row, out, shape, record, start = self._finish.popleft()
            try:
                popped = time.monotonic()
                self.engine.wait_audio(out)
                ready = time.monotonic()
                result = self.engine.fetch_audio(out, row.budget)
                end = time.monotonic()
                row.span(start, end, kind="vocode", rows=1,
                         frames_needed=row.budget,
                         fetch_wait_ms=round((ready - popped) * 1e3, 3),
                         finish_ms=round((end - ready) * 1e3, 3), **shape)
                if row.dump is not None:
                    self._write_dump(row, record, shape)
                row.future.set_result(result)
            except Exception as e:  # one row's failure is that row's
                log.exception("vocoding a row failed")
                row.future.set_exception(OperationError(
                    f"vocoding failed: {type(e).__name__}: {e}"))

    def _write_dump(self, row: Row, record, shape: dict) -> None:
        """What the timed path produced for a flagged row, for whoever
        holds it against a reference: what the engine makes of the row's
        record and of the launches kept (``engine.dump``), and the frame
        bucket the vocoder padded it to."""
        kept = [(launch, [np.asarray(a) if j is None else np.asarray(a)[j]
                          for a in arrays])
                for launch, (arrays, j) in row.dump]
        arrays = self.engine.dump(row.ids, row.budget, kept, record)
        self._dump_dir.mkdir(parents=True, exist_ok=True)
        path = self._dump_dir / f"{row.request_id}.{id(row):x}.npz"
        with open(path, "wb") as f:
            np.savez(f, frames_bucket=np.int32(shape["frames_bucket"]),
                     **arrays)
