"""Graceful-drain tests (ISSUE 9 tentpole piece 1).

Pins the rolling-restart contract end to end at the service level:

- readiness flips off FIRST and new admissions fail **typed** —
  UNAVAILABLE with a ``draining`` detail, never RESOURCE_EXHAUSTED, so
  clients and the degradation ladder can tell a deploy from overload;
- in-flight streams finish with full audio while the drain waits,
  bounded by ``SONATA_DRAIN_TIMEOUT_S``;
- the teardown runs in the pinned :data:`DRAIN_PHASES` order, one
  structured log line per phase;
- a warmup finishing mid-drain can never re-flip readiness (the PR-2
  ``_draining`` pin, extended to the drain path);
- the drain-vs-resubmission race class: a breaker trip or half-open
  probe firing against a draining pool refuses fast and typed (see
  also tests/test_replicas.py for the pool-level pins).
"""

import logging
import threading
import time

import pytest

from sonata_tpu.serving import Draining, Overloaded, ServingRuntime
from sonata_tpu.serving.drain import (
    DRAIN_PHASES,
    DrainCoordinator,
    resolve_drain_timeout_s,
)

from voices import write_tiny_voice


class _AbortCalled(Exception):
    def __init__(self, code, msg):
        super().__init__(f"{code}: {msg}")
        self.code = code
        self.msg = msg


class _Ctx:
    def __init__(self, remaining=None):
        self._remaining = remaining

    def time_remaining(self):
        return self._remaining

    def add_callback(self, cb):
        pass

    def abort(self, code, msg):
        raise _AbortCalled(code, msg)


# ---------------------------------------------------------------------------
# coordinator unit behavior
# ---------------------------------------------------------------------------

def test_coordinator_first_caller_wins_and_flag_sticks():
    d = DrainCoordinator(timeout_s=1.0)
    assert not d.draining
    assert d.begin("deploy") is True
    assert d.begin("second") is False  # first caller owns the phases
    assert d.draining and d.reason == "deploy"
    with pytest.raises(Draining) as ei:
        d.raise_if_draining()
    assert "draining" in str(ei.value)


def test_coordinator_typed_error_is_not_overload():
    """The ladder/clients must be able to tell deploys from overload:
    Draining is NOT an Overloaded subclass (no RESOURCE_EXHAUSTED)."""
    assert not issubclass(Draining, Overloaded)


def test_wait_idle_bounded_and_tolerant():
    d = DrainCoordinator(timeout_s=0.2)
    assert d.wait_idle(lambda: True) is True
    t0 = time.monotonic()
    assert d.wait_idle(lambda: False) is False
    assert 0.15 < time.monotonic() - t0 < 2.0
    # a raising predicate reads as not-idle, never aborts the drain
    assert d.wait_idle(lambda: 1 / 0, timeout_s=0.05) is False


def test_drain_timeout_env(monkeypatch):
    monkeypatch.setenv("SONATA_DRAIN_TIMEOUT_S", "7.5")
    assert resolve_drain_timeout_s() == 7.5
    assert resolve_drain_timeout_s(2.0) == 2.0  # explicit arg wins
    monkeypatch.setenv("SONATA_DRAIN_TIMEOUT_S", "garbage")
    assert resolve_drain_timeout_s() == 30.0


def test_runtime_begin_drain_flips_readiness_and_gauge():
    rt = ServingRuntime()
    rt.health.set_ready("test")
    assert rt.registry.get("sonata_draining").get() == 0.0
    assert rt.begin_drain("deploy") is True
    assert rt.begin_drain("again") is False
    assert not rt.health.ready
    assert "draining" in rt.health.reason
    assert rt.registry.get("sonata_draining").get() == 1.0
    rt.close()


# ---------------------------------------------------------------------------
# service-level drain (real tiny voice, module-scoped per test group)
# ---------------------------------------------------------------------------

@pytest.fixture()
def drain_service(tmp_path):
    import grpc

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends import grpc_server as srv

    vdir = tmp_path / "voice"
    vdir.mkdir()
    cfg = str(write_tiny_voice(vdir))
    runtime = ServingRuntime(max_in_flight=4, max_queue_depth=0,
                             request_timeout_s=30.0)
    service = srv.SonataGrpcService(continuous_batching=True,
                                    runtime=runtime)
    info = service.LoadVoice(pb.VoicePath(config_path=cfg), _Ctx())
    service.warmup_and_mark_ready()
    yield service, info.voice_id, grpc, pb
    service.shutdown()


def test_drain_refuses_new_admissions_unavailable(drain_service):
    service, vid, grpc, pb = drain_service
    rt = service.runtime
    shed_before = rt.admission.shed_total
    assert service.drain(reason="test") is True
    with pytest.raises(_AbortCalled) as ei:
        list(service.SynthesizeUtterance(
            pb.Utterance(voice_id=vid, text="Too late."), _Ctx()))
    assert ei.value.code == grpc.StatusCode.UNAVAILABLE
    assert "draining" in ei.value.msg
    # a deploy is not overload: no shed counted, no slot consumed
    assert rt.admission.shed_total == shed_before
    assert rt.admission.in_flight == 0


def test_drain_waits_for_in_flight_and_runs_pinned_phases(
        drain_service, caplog):
    """The acceptance triangle: in-flight stream finishes with full
    audio, readiness drops before teardown, phases run in the pinned
    order with one log line each."""
    service, vid, grpc, pb = drain_service
    rt = service.runtime
    v = service._voices[vid]
    real = v.voice.speak_batch
    started, release = threading.Event(), threading.Event()
    # the test waits on what it sets and on what the drain thread says of
    # itself (the phases it notes), never on the clock: ``GUARD`` only
    # keeps a broken run from hanging, and a loaded machine does not meet it
    GUARD = 300.0
    noted = {phase: threading.Event() for phase in DRAIN_PHASES}
    note = rt.drain.note_phase

    def noting(phase, **fields):
        note(phase, **fields)
        noted[phase].set()

    rt.drain.note_phase = noting

    def slow(s, speakers=None, scales=None):
        started.set()
        assert release.wait(GUARD)
        return real(s, speakers=speakers, scales=scales)

    v.voice.speak_batch = slow
    results = {}

    def req():
        results["items"] = list(service.SynthesizeUtterance(
            pb.Utterance(voice_id=vid, text="In flight sentence."),
            _Ctx()))

    t = threading.Thread(target=req)
    t.start()
    assert started.wait(GUARD)
    drained = {}
    with caplog.at_level(logging.WARNING, logger="sonata.serving"):
        dt = threading.Thread(
            target=lambda: drained.update(rc=service.drain(
                reason="t", timeout_s=GUARD)))
        dt.start()
        # the drain thread has turned readiness off and refuses admissions:
        # what is left to it is the wait for the request in flight
        assert noted["reject-admissions"].wait(GUARD)
        # readiness off while the in-flight request is still running
        assert not rt.health.ready
        assert dt.is_alive() and t.is_alive()
        assert not noted["wait-in-flight"].is_set()
        release.set()
        t.join(GUARD)
        dt.join(GUARD)
        assert not t.is_alive() and not dt.is_alive()
    assert drained["rc"] is True
    assert results["items"] and len(results["items"][0].wav_samples) > 0
    phases = [p for p, _ms in rt.drain.phases]
    assert phases == list(DRAIN_PHASES)
    # one structured log line per phase, in order
    drain_lines = [r.getMessage() for r in caplog.records
                   if r.getMessage().startswith("drain: phase=")]
    seen = [line.split("phase=")[1].split()[0] for line in drain_lines]
    assert seen == list(DRAIN_PHASES)


def test_drain_timeout_tears_down_with_stragglers(drain_service, caplog):
    """A stream stuck past SONATA_DRAIN_TIMEOUT_S must not hold the
    restart hostage: the drain proceeds to teardown, the straggler
    fails typed when its scheduler shuts down, readiness stays off."""
    service, vid, grpc, pb = drain_service
    rt = service.runtime
    v = service._voices[vid]
    release = threading.Event()
    started = threading.Event()
    real = v.voice.speak_batch

    def wedge(s, speakers=None, scales=None):
        started.set()
        release.wait(20.0)
        return real(s, speakers=speakers, scales=scales)

    v.voice.speak_batch = wedge
    outcome = {}

    def req():
        try:
            outcome["items"] = list(service.SynthesizeUtterance(
                pb.Utterance(voice_id=vid, text="Wedged."), _Ctx()))
        except _AbortCalled as e:
            outcome["err"] = e

    t = threading.Thread(target=req)
    t.start()
    assert started.wait(5.0)
    with caplog.at_level(logging.ERROR, logger="sonata.serving"):
        t0 = time.monotonic()
        assert service.drain(timeout_s=0.3, reason="t") is True
        assert time.monotonic() - t0 < 10.0  # bounded, not hostage
    assert any("still in flight" in r.getMessage()
               for r in caplog.records)
    release.set()
    t.join(10.0)
    # the straggler failed typed (scheduler shut down), not hung
    assert "err" in outcome or "items" in outcome
    assert not rt.health.ready


def test_drain_is_first_caller_wins(drain_service):
    service, _vid, _grpc, _pb = drain_service
    assert service.drain(reason="one") is True
    assert service.drain(reason="two") is False


def test_warmup_finishing_during_drain_never_reflips_ready(tmp_path):
    """PR-2 pin extended to the drain path AND the lattice path: a
    warmup (legacy or lattice) that finishes after drain() began must
    leave readiness false."""
    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends import grpc_server as srv

    vdir = tmp_path / "voice"
    vdir.mkdir()
    cfg = str(write_tiny_voice(vdir))
    service = srv.SonataGrpcService(continuous_batching=True)
    service.LoadVoice(pb.VoicePath(config_path=cfg), _Ctx())
    assert service.drain(reason="deploy") is True
    service.warmup_and_mark_ready()  # voices already closed: instant
    assert not service.runtime.health.ready
    service.shutdown()


def test_shutdown_arms_drain_flag_for_typed_refusals(drain_service):
    """The immediate shutdown() path shares the drain flag, so a
    request racing an abrupt stop still gets the typed UNAVAILABLE."""
    service, vid, grpc, pb = drain_service
    service.shutdown()
    assert service.runtime.drain.draining
    with pytest.raises(_AbortCalled) as ei:
        list(service.SynthesizeUtterance(
            pb.Utterance(voice_id=vid, text="Racing."), _Ctx()))
    assert ei.value.code == grpc.StatusCode.UNAVAILABLE


def test_load_voice_refused_while_draining(drain_service, tmp_path):
    """A LoadVoice racing the drain would hand the teardown a fresh
    voice to miss: refused typed like admissions."""
    service, _vid, grpc, pb = drain_service
    from voices import write_tiny_voice

    vdir = tmp_path / "late_voice"
    vdir.mkdir()
    other = str(write_tiny_voice(vdir, seed=3))
    assert service.drain(reason="deploy") is True
    with pytest.raises(_AbortCalled) as ei:
        service.LoadVoice(pb.VoicePath(config_path=other), _Ctx())
    assert ei.value.code == grpc.StatusCode.UNAVAILABLE
    assert "draining" in ei.value.msg
