"""Replica pool: routing, circuit breaking, failover, drain, devices.

Runs on the forced multi-device CPU host (conftest forces 8 virtual
devices; the CI multi-device lane re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  FakeModel
pools cover the router/breaker state machine in milliseconds; the
device-placement and distribution tests use real tiny voices so the
dispatches actually land on distinct XLA devices.
"""

from __future__ import annotations

import threading
import time

import pytest

from sonata_tpu.core import OperationError
from sonata_tpu.serving import Deadline, DeadlineExceeded, Overloaded
from sonata_tpu.serving.health import HealthState
from sonata_tpu.serving.replicas import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    Replica,
    ReplicaPool,
    resolve_replica_count,
)
from sonata_tpu.testing import FakeModel

from voices import tiny_voice

# per-request dispatch, no gather wait: the state-machine tests want
# deterministic one-item dispatches, not timing-dependent coalescing
SCHED = {"max_batch": 1, "max_wait_ms": 0.0}


class BlockingModel(FakeModel):
    """speak_batch blocks until released (router/queue tests)."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()

    def speak_batch(self, *args, **kwargs):
        assert self.gate.wait(timeout=30), "test forgot to release gate"
        return super().speak_batch(*args, **kwargs)


class FlakyModel(FakeModel):
    """speak_batch fails while ``fail`` is set (breaker tests)."""

    def __init__(self):
        super().__init__()
        self.fail = False

    def speak_batch(self, *args, **kwargs):
        if self.fail:
            raise RuntimeError("injected dispatch failure")
        return super().speak_batch(*args, **kwargs)


def make_pool(models, **kwargs):
    kwargs.setdefault("scheduler_kwargs", SCHED)
    return ReplicaPool(models, **kwargs)


# ---------------------------------------------------------------------------
# sizing
# ---------------------------------------------------------------------------

def test_resolve_replica_count_env(monkeypatch):
    monkeypatch.delenv("SONATA_REPLICAS", raising=False)
    assert resolve_replica_count(None, n_devices=8) == 8
    assert resolve_replica_count(3, n_devices=8) == 3
    monkeypatch.setenv("SONATA_REPLICAS", "2")
    assert resolve_replica_count(None, n_devices=8) == 2
    assert resolve_replica_count(5, n_devices=8) == 5  # explicit beats env
    monkeypatch.setenv("SONATA_REPLICAS", "junk")
    assert resolve_replica_count(None, n_devices=4) == 4


def test_resolve_replica_count_rejects_more_than_devices(monkeypatch):
    """An explicit count above the device count — by argument or by
    ``SONATA_REPLICAS`` — is an error, never a silent clamp."""
    monkeypatch.delenv("SONATA_REPLICAS", raising=False)
    with pytest.raises(OperationError, match="4 replicas .* 1 local"):
        resolve_replica_count(4, n_devices=1)
    monkeypatch.setenv("SONATA_REPLICAS", "9")
    with pytest.raises(OperationError, match="9 replicas .* 8 local"):
        resolve_replica_count(None, n_devices=8)
    assert resolve_replica_count(8, n_devices=8) == 8


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def test_router_least_loaded_invariant():
    """With every dispatch blocked, 2N submits spread exactly 2 per
    replica — the router always picks the least outstanding."""
    models = [BlockingModel() for _ in range(4)]
    pool = make_pool(models)
    try:
        futures = [pool.submit(f"sentence {i}") for i in range(8)]
        assert [r.outstanding for r in pool.replicas] == [2, 2, 2, 2]
        for m in models:
            m.gate.set()
        for fut in futures:
            fut.result(timeout=30)
        assert [r.outstanding for r in pool.replicas] == [0, 0, 0, 0]
        assert pool.stats["routed"] == 8
        assert all(r.dispatches == 2 for r in pool.replicas)
    finally:
        pool.shutdown()


def test_speak_many_returns_in_input_order():
    pool = make_pool([FakeModel() for _ in range(3)])
    try:
        sentences = ["a" * n for n in (2, 9, 4, 7, 1, 5)]
        audios = pool.speak_many(sentences, timeout=30)
        # FakeModel length scales with phoneme count: order must match
        lengths = [len(a.samples) for a in audios]
        expected = [len(FakeModel().speak_one_sentence(s).samples)
                    for s in sentences]
        assert lengths == expected
    finally:
        pool.shutdown()


def test_batched_stream_carries_voice_config_through_pool():
    """The original voice's fallback config (SetSynthesisOptions / CLI
    scales) must travel to the pool as per-request scales — the replica
    copies' own configs never see mutations on the original."""
    from sonata_tpu.synth import SpeechSynthesizer

    orig = FakeModel()
    pool = make_pool([FakeModel(), FakeModel()])
    try:
        synth = SpeechSynthesizer(orig, replica_pool=pool)
        sc = orig.get_fallback_synthesis_config()
        sc.length_scale = 2.0
        orig.set_fallback_synthesis_config(sc)
        text = "Hello there."
        base = sum(len(a.samples) for a in
                   SpeechSynthesizer(FakeModel()).synthesize_parallel(text))
        pooled = sum(len(a.samples) for a in synth.synthesize_parallel(text))
        assert pooled == 2 * base
    finally:
        pool.shutdown()


def test_grpc_service_rejects_env_replicas_with_mesh(monkeypatch):
    """SONATA_REPLICAS must not smuggle a pool past the replicas/mesh
    mutual exclusion (the flag path is checked the same way)."""
    pytest.importorskip("grpc")
    import jax

    from sonata_tpu.frontends.grpc_server import SonataGrpcService
    from sonata_tpu.parallel import make_mesh

    monkeypatch.setenv("SONATA_REPLICAS", "2")
    with pytest.raises(OperationError, match="mutually exclusive"):
        SonataGrpcService(mesh=make_mesh(len(jax.local_devices())))


def test_deadline_expires_inside_replica_queue():
    """An item stuck behind a blocked dispatch is dropped on expiry
    BEFORE it reaches the device — the scheduler contract holds through
    the pool (a dead deadline is the request's fault, never resubmitted)."""
    model = BlockingModel()
    pool = make_pool([model])
    try:
        first = pool.submit("blocker")
        doomed = pool.submit("too late", deadline=Deadline.after(0.05))
        time.sleep(0.2)
        model.gate.set()
        first.result(timeout=30)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=30)
        assert pool.stats["resubmitted"] == 0
        assert pool.stats_view()["expired"] == 1
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# breaker
# ---------------------------------------------------------------------------

def test_breaker_opens_after_threshold_and_fails_over():
    models = [FlakyModel(), FlakyModel()]
    pool = make_pool(models, breaker_threshold=3, probe_interval_s=60)
    try:
        models[0].fail = True
        # drive enough traffic that replica 0 eats >= 3 dispatch failures
        audios = pool.speak_many([f"s{i}" for i in range(12)], timeout=30)
        assert len(audios) == 12  # every request served — no client errors
        assert pool.replicas[0].state == OPEN
        assert pool.replicas[1].state == CLOSED
        assert pool.healthy_count() == 1
        assert pool.stats["breaker_opens"] == 1
        assert pool.stats["resubmitted"] >= 3
        assert pool.stats["failed"] == 0
        # an open replica receives no further traffic
        routed_before = pool.replicas[0].submitted
        pool.speak_many(["t1", "t2"], timeout=30)
        assert pool.replicas[0].submitted == routed_before
    finally:
        pool.shutdown()


def test_breaker_half_open_probe_closes_on_success():
    models = [FlakyModel(), FlakyModel()]
    pool = make_pool(models, breaker_threshold=2, probe_interval_s=0.15)
    try:
        models[0].fail = True
        pool.speak_many([f"s{i}" for i in range(8)], timeout=30)
        assert pool.replicas[0].state == OPEN
        models[0].fail = False  # chip recovers
        deadline = time.monotonic() + 10
        while (pool.replicas[0].state != HALF_OPEN
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert pool.replicas[0].state == HALF_OPEN
        assert pool.healthy_count() == 2  # half-open counts as routable
        # the next request is the trial; success closes the breaker
        pool.speak("trial", timeout=30)
        assert pool.replicas[0].state == CLOSED
        assert pool.stats["recovered"] == 1
    finally:
        pool.shutdown()


def test_breaker_half_open_reopens_on_failed_trial():
    models = [FlakyModel(), FlakyModel()]
    pool = make_pool(models, breaker_threshold=2, probe_interval_s=0.15)
    try:
        models[0].fail = True
        pool.speak_many([f"s{i}" for i in range(8)], timeout=30)
        assert pool.replicas[0].state == OPEN
        opens_before = pool.stats["breaker_opens"]
        deadline = time.monotonic() + 10
        while (pool.replicas[0].state != HALF_OPEN
               and time.monotonic() < deadline):
            time.sleep(0.02)
        # still failing: the trial request must reopen the breaker
        # immediately (one failure, not another full threshold's worth)
        # and still be answered by the healthy replica
        audio = pool.speak("trial", timeout=30)
        assert len(audio.samples) > 0
        assert pool.replicas[0].state == OPEN
        assert pool.stats["breaker_opens"] == opens_before + 1
    finally:
        pool.shutdown()


def test_resubmission_is_exactly_once():
    """Both replicas broken mid-flight: the request is resubmitted once,
    then the client sees the error — never an infinite relay."""
    models = [FlakyModel(), FlakyModel()]
    pool = make_pool(models, breaker_threshold=99, probe_interval_s=60)
    try:
        for m in models:
            m.fail = True
        fut = pool.submit("doomed")
        with pytest.raises(RuntimeError, match="injected"):
            fut.result(timeout=30)
        assert pool.stats["resubmitted"] == 1
        assert pool.stats["failed"] == 1
    finally:
        pool.shutdown()


def test_no_healthy_replicas_sheds_and_flips_readiness_gate():
    health = HealthState()
    models = [FlakyModel(), FlakyModel()]
    # probe long enough that the immediate assertions below run while
    # both breakers are still open, short enough that recovery happens
    pool = make_pool(models, breaker_threshold=1, probe_interval_s=0.5)
    health.add_readiness_gate("replicas:test",
                              lambda: pool.healthy_count() > 0)
    health.set_ready("warmed")
    try:
        assert health.ready
        for m in models:
            m.fail = True
        with pytest.raises(RuntimeError):
            pool.speak("x", timeout=30)
        assert pool.healthy_count() == 0
        assert not health.ready  # zero healthy replicas flips /readyz
        assert "replicas:test" in health.reason
        # new work is shed with Overloaded (maps to RESOURCE_EXHAUSTED)
        with pytest.raises(Overloaded):
            pool.submit("y").result(timeout=30)
        # recovery un-flips readiness with no set_ready call
        pool.force_open(0, "noop")  # already open; exercise idempotence
        for m in models:
            m.fail = False
        deadline = time.monotonic() + 10
        while not health.ready and time.monotonic() < deadline:
            time.sleep(0.02)  # probe loop flips replicas half-open
        assert health.ready
        health.remove_readiness_gate("replicas:test")
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# drain / shutdown
# ---------------------------------------------------------------------------

def test_shutdown_drains_queued_work():
    model = BlockingModel()
    pool = make_pool([model])
    blocked = pool.submit("in flight")
    queued = pool.submit("queued behind")
    pool.shutdown()
    model.gate.set()
    with pytest.raises(Exception):
        queued.result(timeout=30)
    with pytest.raises(OperationError):
        pool.submit("after shutdown")
    # the in-flight item either completed or failed, but never hangs
    try:
        blocked.result(timeout=30)
    except Exception:
        pass


def test_force_open_drains_and_resubmits_queued_work():
    """Breaker drain semantics: queued work on the tripped replica is
    failed out of its scheduler and resubmitted to a healthy one."""
    blocker, healthy = BlockingModel(), FakeModel()
    healthy_gate_open = healthy  # readable alias
    pool = make_pool([blocker, healthy])
    try:
        first = pool.submit("occupies replica 0")   # -> r0 (blocks)
        second = pool.submit("occupies replica 1")  # -> r1 (completes)
        second.result(timeout=30)
        queued = pool.submit("queued on r0")        # r0 least loaded? both
        # ensure at least one item rides replica 0's queue
        extra = [pool.submit(f"x{i}") for i in range(4)]
        pool.force_open(0, "test drain")
        # queued items fail out of r0's scheduler and resubmit to r1
        for fut in [queued, *extra]:
            audio = fut.result(timeout=30)
            assert len(audio.samples) > 0
        assert pool.stats["resubmitted"] >= 1
        blocker.gate.set()
        try:
            first.result(timeout=30)  # in-flight: served or failed over
        except Exception:
            pass
    finally:
        pool.shutdown()


def test_probe_rebuild_does_not_hold_pool_lock():
    """A half-open probe rebuilding a drained replica's scheduler must
    not hold the pool lock across construction — scheduler construction
    resolves the model's dispatch policy, which may run a device probe
    taking seconds, and the lock would stall routing, breaker
    bookkeeping, and health reads on every OTHER replica meanwhile.
    Pinned from the sonata-lint lock-order pass (blocking-under-lock in
    ``_probe_loop``)."""
    pool = make_pool([FakeModel(), FakeModel()], probe_interval_s=0.05)
    entered, release = threading.Event(), threading.Event()
    try:
        r0 = pool.replicas[0]
        real_new_scheduler = r0._new_scheduler

        def slow_new_scheduler():
            entered.set()
            assert release.wait(timeout=30), "test forgot to release"
            return real_new_scheduler()

        r0._new_scheduler = slow_new_scheduler
        pool.force_open(0, "test")
        assert entered.wait(timeout=30), "prober never began the rebuild"
        # construction is in progress on the prober thread: the pool
        # lock must be free — health reads and routing to the healthy
        # replica complete promptly instead of queueing behind it
        probe_result: dict = {}

        def read_health():
            probe_result["healthy"] = pool.healthy_count()
            probe_result["audio"] = pool.speak("still routable",
                                               timeout=10)

        t = threading.Thread(target=read_health, daemon=True)
        t.start()
        t.join(timeout=5.0)
        assert not t.is_alive(), \
            "pool lock held while the probe rebuilt a scheduler"
        assert probe_result["healthy"] == 1
        assert len(probe_result["audio"].samples) > 0
        release.set()
        deadline = time.monotonic() + 30
        while r0.state != HALF_OPEN and time.monotonic() < deadline:
            time.sleep(0.01)
        assert r0.state == HALF_OPEN  # rebuilt scheduler was installed
    finally:
        release.set()
        pool.shutdown()


# ---------------------------------------------------------------------------
# real devices (the acceptance criterion)
# ---------------------------------------------------------------------------

def _param_devices(voice):
    import jax.tree_util as jtu

    leaf = jtu.tree_leaves(voice.params)[0]
    return set(leaf.devices())


def test_replica_for_device_pins_params():
    import jax

    devices = jax.local_devices()[:2]
    v = tiny_voice(seed=40)
    replicas = [v.replica_for_device(d, seed_offset=i)
                for i, d in enumerate(devices)]
    for replica, device in zip(replicas, devices):
        assert _param_devices(replica) == {device}
        assert replica.device is device


def test_replica_for_device_rejects_mesh_voice():
    import jax

    from sonata_tpu.models import PiperVoice
    from sonata_tpu.parallel import make_mesh

    v = tiny_voice(seed=41)
    mesh = make_mesh(len(jax.local_devices()))  # works in the 4-dev lane
    vm = PiperVoice(v.config, v.params, seed=41, mesh=mesh)
    with pytest.raises(OperationError, match="mutually exclusive"):
        vm.replica_for_device(jax.local_devices()[0])


def test_pool_distributes_requests_across_devices():
    """The ISSUE acceptance bar: a 4-replica pool over forced host
    devices serves 32 concurrent requests with every replica's dispatch
    counter nonzero, and injected dispatch failure on one replica
    circuit-breaks it while the rest serve every request."""
    import jax

    n = min(4, len(jax.local_devices()))
    assert n >= 2, "multi-device CPU host required (conftest forces 8)"
    voice = tiny_voice(seed=42)
    pool = ReplicaPool.for_voice(voice, n, breaker_threshold=2,
                                 probe_interval_s=60)
    try:
        assert len(pool.replicas) == n
        assert len({r.device for r in pool.replicas}) == n
        for r in pool.replicas:
            assert _param_devices(r.model._model) == {r.device}
        phon = list(voice.phonemize_text("One request of many."))
        futures = [pool.submit(phon[0]) for _ in range(32)]
        audios = [f.result(timeout=300) for f in futures]
        assert all(len(a.samples) > 0 for a in audios)
        assert all(r.dispatches > 0 for r in pool.replicas), \
            [r.snapshot() for r in pool.replicas]

        # fault injection: kill one replica's dispatch fn
        broken = pool.replicas[0]
        inner = broken.model._model

        def boom(*a, **kw):
            raise RuntimeError("injected device fault")

        inner.speak_batch = boom
        futures = [pool.submit(phon[0]) for _ in range(16)]
        audios = [f.result(timeout=300) for f in futures]
        assert all(len(a.samples) > 0 for a in audios)  # no client errors
        assert broken.state == OPEN
        assert pool.healthy_count() == n - 1
        assert pool.stats["failed"] == 0
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# gRPC integration: per-replica metrics, readiness, UnloadVoice drain
# ---------------------------------------------------------------------------

def test_grpc_replica_pool_end_to_end(tmp_path):
    grpc = pytest.importorskip("grpc")

    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends.grpc_server import create_server
    from sonata_tpu.serving import parse_prometheus_text

    from voices import write_tiny_voice

    cfg = str(write_tiny_voice(tmp_path))
    server, port = create_server(0, replicas=2, request_timeout_s=60.0)
    server.start()
    service = server.sonata_service
    runtime = server.sonata_runtime
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")

        def unary(name, req, resp_cls):
            return channel.unary_unary(
                f"/sonata_grpc.sonata_grpc/{name}",
                request_serializer=lambda m: m.encode(),
                response_deserializer=resp_cls.decode)(req)

        info = unary("LoadVoice", pb.VoicePath(config_path=cfg),
                     pb.VoiceInfo)
        v = service._voices[info.voice_id]
        assert v.pool is not None and len(v.pool.replicas) == 2
        service.warmup_and_mark_ready()
        assert runtime.health.ready
        # warmup ran through EVERY replica, not just the least loaded
        assert all(r.dispatches > 0 for r in v.pool.replicas)

        results = list(channel.unary_stream(
            "/sonata_grpc.sonata_grpc/SynthesizeUtterance",
            request_serializer=lambda m: m.encode(),
            response_deserializer=pb.SynthesisResult.decode)(
            pb.Utterance(voice_id=info.voice_id,
                         text="Replica pool smoke sentence.")))
        assert results and len(results[0].wav_samples) > 0

        parsed = parse_prometheus_text(runtime.registry.render())
        series = parsed["sonata_replica_dispatches"]
        labels = {(s["voice"], s["replica"]) for s, _v in series}
        assert labels == {(info.voice_id, "0"), (info.voice_id, "1")}
        for name in ("sonata_replica_breaker_state",
                     "sonata_replica_outstanding", "sonata_replica_device",
                     "sonata_pool_routed", "sonata_pool_healthy_replicas"):
            assert name in parsed, name

        # one breaker-open replica must NOT flip readiness...
        v.pool.force_open(0, "test")
        assert runtime.health.ready
        # ...but zero healthy replicas must
        v.pool.force_open(1, "test")
        assert not runtime.health.ready

        pool = v.pool
        unary("UnloadVoice", pb.VoiceIdentifier(voice_id=info.voice_id),
              pb.Empty())
        # UnloadVoice drained the pool and removed its gate + series
        with pytest.raises(OperationError):
            pool.submit("x")
        assert runtime.health.ready  # gate removed with the voice
        parsed = parse_prometheus_text(runtime.registry.render())
        assert "sonata_replica_dispatches" not in parsed
    finally:
        server.stop(grace=None)
        service.shutdown()


# ---------------------------------------------------------------------------
# drain-vs-resubmission race class (ISSUE 9): a breaker trip or
# half-open probe firing while the pool is draining must refuse fast
# and typed — no resubmission into a closing scheduler, no orphaned
# probe-built worker thread.  All under the thread-hygiene fixture.
# ---------------------------------------------------------------------------

def test_draining_pool_refuses_new_submits_typed():
    from sonata_tpu.serving.drain import Draining

    pool = make_pool([FakeModel(), FakeModel()])
    try:
        pool.submit("before drain").result(timeout=30)
        pool.start_draining()
        assert pool.draining
        with pytest.raises(Draining) as ei:
            pool.submit("after drain")
        assert "draining" in str(ei.value)
        # typed as a deploy, not overload and not a bare shutdown error
        assert not isinstance(ei.value, Overloaded)
    finally:
        pool.shutdown()


def test_breaker_trip_during_drain_fails_fast_no_resubmission():
    """An in-flight dispatch failing after the drain began must NOT
    resubmit into a closing scheduler: the outer future fails fast with
    the typed Draining, the resubmit counter stays put."""
    from sonata_tpu.serving.drain import Draining

    class GatedFailModel(FakeModel):
        def __init__(self):
            super().__init__()
            self.gate = threading.Event()
            self.entered = threading.Event()

        def speak_batch(self, *args, **kwargs):
            self.entered.set()
            assert self.gate.wait(timeout=30)
            raise RuntimeError("device died mid-drain")

    m0, m1 = GatedFailModel(), GatedFailModel()
    pool = make_pool([m0, m1])
    try:
        fut = pool.submit("doomed")
        # the item is in flight (blocked inside speak_batch) when the
        # drain begins; releasing the gate then fails the dispatch
        deadline = time.monotonic() + 5.0
        while not (m0.entered.is_set() or m1.entered.is_set()) \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert m0.entered.is_set() or m1.entered.is_set()
        pool.start_draining()
        m0.gate.set()
        m1.gate.set()
        t0 = time.monotonic()
        with pytest.raises(Draining) as ei:
            fut.result(timeout=30)
        assert time.monotonic() - t0 < 5.0  # fast, not hung
        assert "not resubmitting" in str(ei.value)
        assert pool.stats["resubmitted"] == 0
    finally:
        pool.shutdown()


def test_half_open_probe_refuses_draining_pool():
    """A probe firing against a draining pool must not rebuild a
    scheduler (whose worker thread nobody would join): the replica
    stays OPEN and the prober exits — the drain is terminal."""
    pool = make_pool([FakeModel(), FakeModel()], probe_interval_s=0.05)
    try:
        built = []
        real_new = Replica._new_scheduler

        def counting_new(self):
            built.append(self.index)
            return real_new(self)

        pool.force_open(0, "test")
        pool.start_draining()
        built.clear()
        for r in pool.replicas:
            r._new_scheduler = counting_new.__get__(r)
        with pool._lock:
            pool.replicas[0].next_probe_at = time.monotonic()
        pool._probe_wake.set()
        deadline = time.monotonic() + 1.0
        while pool._prober.is_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.replicas[0].state == OPEN  # never flipped half-open
        assert built == []                     # no scheduler was built
        assert not pool._prober.is_alive()     # terminal: prober exited
    finally:
        pool.shutdown()


def test_route_racing_drain_surfaces_draining_not_internals():
    """A submit callback racing start_draining + a replica drain used
    to retry other replicas on the raw 'shut down' error; draining it
    must surface the typed Draining instead."""
    from sonata_tpu.serving.drain import Draining

    pool = make_pool([FakeModel()])
    try:
        pool.start_draining()
        # simulate the raced path directly: _route on a draining pool
        # whose replica scheduler is already closing
        pool.replicas[0].scheduler.shutdown()
        from concurrent.futures import Future

        outer = Future()
        pool._route(outer, "raced", None, None, None,
                    resubmits_left=1, exclude=())
        with pytest.raises(Draining):
            outer.result(timeout=5)
    finally:
        pool.shutdown()
