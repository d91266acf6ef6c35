"""Bucket-lattice AOT warmup tests (ISSUE 9 tentpole piece 2 + 3).

Pins the lattice warmup contract:

- ``SONATA_WARMUP_LATTICE`` mode semantics: ``minimal`` is a strict
  subset of ``full``; garbage fails loudly at boot; ``off`` keeps the
  legacy one-utterance warmup (and does NOT arm cold-compile
  containment);
- budget expiry (``SONATA_WARMUP_BUDGET_S``) leaves readiness **false**
  with one loud log line — a half-warm replica never joins the set;
- per-replica coverage: EVERY replica's model warms the lattice, not
  just replica 0;
- a warmup finishing during a drain cannot re-flip readiness (the PR-2
  ``_draining`` pin extended to the lattice path);
- cold-compile containment: a ``compile=cold`` dispatch after warmup
  completion counts ``sonata_runtime_cold_compiles_total{voice}`` and
  lands a flight-recorder incident.
"""

import logging
import threading
import time

import pytest

from sonata_tpu.core import OperationError
from sonata_tpu.models import PiperVoice
from sonata_tpu.serving import ServingRuntime
from sonata_tpu.serving import warmup as warmup_mod
from sonata_tpu.serving.scope import Scope
from sonata_tpu.serving.warmup import (
    WarmupBudgetExceeded,
    WarmupProgress,
    resolve_budget_s,
    resolve_mode,
    warm_model_lattice,
)
from sonata_tpu.testing import FakeModel
from sonata_tpu.utils.buckets import FRAME_BUCKETS, TEXT_BUCKETS

from voices import tiny_voice, write_tiny_voice


class _AbortCalled(Exception):
    def __init__(self, code, msg):
        super().__init__(f"{code}: {msg}")
        self.code = code
        self.msg = msg


class _Ctx:
    def time_remaining(self):
        return None

    def add_callback(self, cb):
        pass

    def abort(self, code, msg):
        raise _AbortCalled(code, msg)


# ---------------------------------------------------------------------------
# knobs + progress
# ---------------------------------------------------------------------------

def test_resolve_mode_env_and_validation(monkeypatch):
    monkeypatch.delenv("SONATA_WARMUP_LATTICE", raising=False)
    assert resolve_mode() == "full"  # production default
    monkeypatch.setenv("SONATA_WARMUP_LATTICE", "minimal")
    assert resolve_mode() == "minimal"
    assert resolve_mode("off") == "off"  # explicit arg wins
    monkeypatch.setenv("SONATA_WARMUP_LATTICE", "fulll")
    with pytest.raises(OperationError):
        resolve_mode()  # a typo'd mode fails LOUDLY at boot


def test_resolve_budget_env(monkeypatch):
    monkeypatch.setenv("SONATA_WARMUP_BUDGET_S", "12.5")
    assert resolve_budget_s() == 12.5
    assert resolve_budget_s(3.0) == 3.0
    monkeypatch.setenv("SONATA_WARMUP_BUDGET_S", "nope")
    assert resolve_budget_s() == warmup_mod.DEFAULT_WARMUP_BUDGET_S


def test_progress_fraction_math():
    p = WarmupProgress()
    assert p.fraction() == 0.0  # boot: nothing warmed, nothing finished
    p.reset()
    p.add_total(4)
    assert p.fraction() == 0.0
    p.note_done(3)
    assert p.fraction() == 0.75
    p.note_done()
    assert p.fraction() == 1.0
    p2 = WarmupProgress()
    p2.reset()
    p2.finish()  # no lattice enumerated (mode off): finished reads 1.0
    assert p2.fraction() == 1.0
    assert p2.snapshot()["finished"] is True


# ---------------------------------------------------------------------------
# lattice semantics (fake + real voice)
# ---------------------------------------------------------------------------

def test_fake_lattice_minimal_subset_and_off():
    fm = FakeModel()
    mini, full = fm.lattice_shapes("minimal"), fm.lattice_shapes("full")
    assert set(mini) < set(full)
    assert fm.lattice_shapes("off") == []
    warm_model_lattice(fm, mode="minimal",
                       deadline=time.monotonic() + 10.0)
    assert fm.warmed_shapes == mini  # warmed in enumeration order


def test_warm_model_lattice_without_contract_is_zero():
    class Legacy:
        pass

    assert warm_model_lattice(Legacy(), mode="full",
                              deadline=time.monotonic() + 1.0) == 0


def test_budget_expiry_raises_typed_mid_lattice():
    """The compile pool runs WARM_WORKERS wide, so the first wave (4 of
    the fake's 5 shapes) starts inside the budget and finishes; the 5th
    re-checks the deadline on its worker, finds it blown, and the whole
    lattice raises typed — partial coverage stays honestly below 1.0."""
    fm = FakeModel()
    fm.warm_delay_s = 0.15
    progress = WarmupProgress()
    progress.reset()
    with pytest.raises(WarmupBudgetExceeded):
        warm_model_lattice(fm, mode="full",
                           deadline=time.monotonic() + 0.08,
                           progress=progress, workers=4)
    # partial coverage recorded honestly (a budget gauge below 1.0)
    assert 0 < len(fm.warmed_shapes) < len(fm.lattice_shapes("full"))
    assert progress.fraction() < 1.0


def test_resolve_workers_env(monkeypatch):
    from sonata_tpu.serving.warmup import resolve_workers

    monkeypatch.delenv("SONATA_WARMUP_WORKERS", raising=False)
    assert resolve_workers() == 4
    monkeypatch.setenv("SONATA_WARMUP_WORKERS", "1")
    assert resolve_workers() == 1
    assert resolve_workers(2) == 2  # explicit arg wins
    monkeypatch.setenv("SONATA_WARMUP_WORKERS", "junk")
    assert resolve_workers() == 4
    monkeypatch.setenv("SONATA_WARMUP_WORKERS", "0")
    assert resolve_workers() == 1  # floored


def test_real_voice_lattice_shapes_are_valid_buckets():
    v = tiny_voice(seed=7)
    mini = v.lattice_shapes("minimal")
    full = v.lattice_shapes("full")
    assert set(mini) <= set(full)
    assert v.lattice_shapes("off") == []
    # minimal: batch-1 only, every text bucket covered with the
    # estimator-reachable frame-bucket RANGE (a sentence sits anywhere
    # in its text bucket's id-length span) plus the up-neighbor
    assert {b for b, _t, _f in mini} == {1}
    assert {t for _b, t, _f in mini} == set(TEXT_BUCKETS)
    by_text: dict = {}
    for _b, t, f in mini:
        by_text.setdefault(t, set()).add(f)
    for t, fs in by_text.items():
        idx = sorted(FRAME_BUCKETS.index(f) for f in fs
                     if f in FRAME_BUCKETS)
        # a contiguous run of frame buckets, never a sparse scatter
        assert idx == list(range(idx[0], idx[-1] + 1)), (t, fs)
    for _b, t, f in full:
        assert t in TEXT_BUCKETS
        assert f in FRAME_BUCKETS or f % FRAME_BUCKETS[-1] == 0


def test_real_voice_warm_shape_compiles_the_cached_fn():
    v = tiny_voice(seed=7)
    shape = v.lattice_shapes("minimal")[0]
    assert (shape[0], shape[1], shape[2]) not in v._full_cache
    v.warm_shape(shape)
    assert (shape[0], shape[1], shape[2]) in v._full_cache


def test_warm_shape_never_feeds_the_frame_estimator():
    """warm_shape must bypass the estimator's observe: zero-input dummy
    runs would corrupt what the lattice was enumerated with."""
    v = tiny_voice(seed=7)
    before = v.frame_estimator.frames_per_id
    observed_before = v.frame_estimator.observed
    v.warm_shape((1, 16, 64))
    assert v.frame_estimator.frames_per_id == before
    assert v.frame_estimator.observed == observed_before


# ---------------------------------------------------------------------------
# service-level: readiness gating, per-replica coverage, drain pin
# ---------------------------------------------------------------------------

def _tiny_cfg(tmp_path):
    vdir = tmp_path / "voice"
    vdir.mkdir()
    return str(write_tiny_voice(vdir))


@pytest.fixture()
def patched_lattice(monkeypatch):
    """Replace the real (expensive) lattice with a 2-shape stub that
    records WHICH model instance warmed — the per-replica coverage
    probe — while the calibration utterance still runs for real."""
    warmed = []
    monkeypatch.setattr(
        PiperVoice, "lattice_shapes",
        lambda self, mode="full": ([(1, 16, 64)] if mode == "minimal"
                                   else [(1, 16, 64), (1, 32, 128)]))
    monkeypatch.setattr(
        PiperVoice, "warm_shape",
        lambda self, shape: warmed.append((id(self), tuple(shape))))
    return warmed


def test_warmup_lattice_runs_and_arms_containment(
        tmp_path, monkeypatch, patched_lattice):
    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends import grpc_server as srv

    monkeypatch.setenv("SONATA_WARMUP_LATTICE", "full")
    service = srv.SonataGrpcService(continuous_batching=True)
    service.LoadVoice(pb.VoicePath(config_path=_tiny_cfg(tmp_path)),
                      _Ctx())
    service.warmup_and_mark_ready()
    assert service.runtime.health.ready
    assert [s for _m, s in patched_lattice] == [(1, 16, 64), (1, 32, 128)]
    assert service.runtime.warmup_progress.fraction() == 1.0
    if service.runtime.scope is not None:
        assert service.runtime.scope.warmup_complete
    service.shutdown()


def test_warmup_off_keeps_legacy_and_does_not_arm(
        tmp_path, monkeypatch, patched_lattice):
    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends import grpc_server as srv

    monkeypatch.setenv("SONATA_WARMUP_LATTICE", "off")
    service = srv.SonataGrpcService(continuous_batching=True)
    service.LoadVoice(pb.VoicePath(config_path=_tiny_cfg(tmp_path)),
                      _Ctx())
    service.warmup_and_mark_ready()
    assert service.runtime.health.ready
    assert patched_lattice == []  # legacy warmup only
    # mode=off makes no coverage promise: containment stays unarmed
    if service.runtime.scope is not None:
        assert not service.runtime.scope.warmup_complete
    service.shutdown()


def test_budget_expiry_leaves_readiness_false_loudly(
        tmp_path, monkeypatch, caplog):
    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends import grpc_server as srv

    monkeypatch.setenv("SONATA_WARMUP_LATTICE", "full")
    monkeypatch.setenv("SONATA_WARMUP_BUDGET_S", "0.05")
    monkeypatch.setattr(PiperVoice, "lattice_shapes",
                        lambda self, mode="full": [(1, 16, 64)])
    monkeypatch.setattr(
        PiperVoice, "warm_shape",
        lambda self, shape: time.sleep(0.2))
    service = srv.SonataGrpcService(continuous_batching=True)
    service.LoadVoice(pb.VoicePath(config_path=_tiny_cfg(tmp_path)),
                      _Ctx())
    with caplog.at_level(logging.ERROR, logger="sonata.grpc"):
        service.warmup_and_mark_ready()
    assert not service.runtime.health.ready
    assert any("readiness stays false" in r.getMessage()
               for r in caplog.records)
    snap = service.runtime.warmup_progress.snapshot()
    assert snap["failed_reason"]
    # containment never armed: the lattice did not complete
    if service.runtime.scope is not None:
        assert not service.runtime.scope.warmup_complete
    service.shutdown()


def test_every_replica_warms_not_just_replica_zero(
        tmp_path, monkeypatch, patched_lattice):
    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends import grpc_server as srv

    monkeypatch.setenv("SONATA_WARMUP_LATTICE", "minimal")
    service = srv.SonataGrpcService(replicas=2)
    info = service.LoadVoice(
        pb.VoicePath(config_path=_tiny_cfg(tmp_path)), _Ctx())
    v = service._voices[info.voice_id]
    assert v.pool is not None and len(v.pool.replicas) == 2
    service.warmup_and_mark_ready()
    assert service.runtime.health.ready
    # every replica's device-pinned model warmed its lattice
    models_warmed = {m for m, _s in patched_lattice}
    assert len(models_warmed) == 2, patched_lattice
    per_model = {m: [s for mm, s in patched_lattice if mm == m]
                 for m in models_warmed}
    assert all(shapes == [(1, 16, 64)] for shapes in per_model.values())
    service.shutdown()


def test_lattice_warmup_finishing_during_drain_stays_not_ready(
        tmp_path, monkeypatch):
    """The PR-2 pin extended to the lattice path: a drain beginning
    while the lattice is mid-compile wins — the late warmup completion
    must not re-flip readiness."""
    from sonata_tpu.frontends import grpc_messages as pb
    from sonata_tpu.frontends import grpc_server as srv

    monkeypatch.setenv("SONATA_WARMUP_LATTICE", "full")
    in_warm, release = threading.Event(), threading.Event()
    monkeypatch.setattr(PiperVoice, "lattice_shapes",
                        lambda self, mode="full": [(1, 16, 64)])

    def slow_warm(self, shape):
        in_warm.set()
        release.wait(10.0)

    monkeypatch.setattr(PiperVoice, "warm_shape", slow_warm)
    service = srv.SonataGrpcService(continuous_batching=True)
    service.LoadVoice(pb.VoicePath(config_path=_tiny_cfg(tmp_path)),
                      _Ctx())
    t = threading.Thread(target=service.warmup_and_mark_ready)
    t.start()
    assert in_warm.wait(10.0)
    assert service.drain(timeout_s=0.2, reason="deploy") is True
    release.set()
    t.join(10.0)
    assert not service.runtime.health.ready
    service.shutdown()


# ---------------------------------------------------------------------------
# cold-compile containment (scope plane)
# ---------------------------------------------------------------------------

def test_runtime_cold_compiles_counted_only_after_warmup(tmp_path):
    scope = Scope(dump_dir=str(tmp_path / "dumps"))
    attrs = {"voice": "v1", "compile": "cold", "padding_ratio": 0.0,
             "batch_bucket": 1, "text_bucket": 16, "frame_bucket": 64,
             "rows": 1, "padding_rows": 0}
    scope.note_dispatch(0.1, dict(attrs))  # during warmup: not runtime
    assert scope.runtime_cold_compiles("v1") == 0
    assert scope.cold_compiles_total == 1
    scope.mark_warmup_complete()
    scope.note_dispatch(0.1, dict(attrs))
    assert scope.runtime_cold_compiles("v1") == 1.0
    assert scope.runtime_cold_compiles_total() == 1
    # cached dispatches never count
    scope.note_dispatch(0.1, {**attrs, "compile": "cached"})
    assert scope.runtime_cold_compiles_total() == 1
    # the incident shipped the flight recorder (rate-limited per reason)
    assert scope.dumps and "cold-compile" in scope.dumps[0]
    scope.close()


def test_voice_loaded_after_warmup_does_not_false_alarm(tmp_path):
    """A voice legitimately loaded via LoadVoice AFTER boot readiness
    made no lattice promise: its first compiles must not count as
    runtime cold compiles or dump incidents — only voices the boot
    warmup actually covered are armed."""
    scope = Scope(dump_dir=str(tmp_path / "dumps"))
    base = {"compile": "cold", "padding_ratio": 0.0, "batch_bucket": 1,
            "text_bucket": 16, "frame_bucket": 64}
    scope.mark_warmup_complete(voices=["warmed-voice"])
    scope.note_dispatch(0.1, {**base, "voice": "latecomer"})
    assert scope.runtime_cold_compiles("latecomer") == 0
    assert scope.runtime_cold_compiles_total() == 0
    assert not scope.dumps  # no false incident either
    scope.note_dispatch(0.1, {**base, "voice": "warmed-voice"})
    assert scope.runtime_cold_compiles("warmed-voice") == 1.0
    assert scope.dumps
    scope.close()


def test_runtime_cold_compiles_exported_per_voice(tmp_path):
    scope = Scope(dump_dir=None)
    rt = ServingRuntime(scope=scope)
    rt.register_voice("v9", rtf_counter=None)
    scope.mark_warmup_complete()
    scope.note_dispatch(0.1, {"voice": "v9", "compile": "cold",
                              "padding_ratio": 0.0, "batch_bucket": 1,
                              "text_bucket": 16, "frame_bucket": 64})
    from sonata_tpu.serving import parse_prometheus_text

    parsed = parse_prometheus_text(rt.registry.render())
    series = parsed.get("sonata_runtime_cold_compiles_total", [])
    assert ({"voice": "v9"}, 1.0) in series, series
    # unregister removes exactly the registered series
    rt.unregister_voice("v9")
    parsed = parse_prometheus_text(rt.registry.render())
    assert not parsed.get("sonata_runtime_cold_compiles_total")
    rt.close()


# ---------------------------------------------------------------------------
# AOT executable store (utils/jax_cache.aot_cache_dir + warm_shape)
# ---------------------------------------------------------------------------

def test_warm_shape_aot_roundtrip_and_numerics(tmp_path, monkeypatch,
                                               caplog):
    """Cold warm_shape serializes the compiled executable; a fresh
    process-equivalent (new voice instance) loads it with zero
    retracing, installs it in the SAME cache traffic dispatches
    through, and real synthesis through it is bit-identical to the jit
    path."""
    import numpy as np

    monkeypatch.setenv("SONATA_AOT_CACHE", str(tmp_path / "aot"))
    v = tiny_voice(seed=11)
    v.warm_shape((1, 16, 64))
    blobs = list((tmp_path / "aot").glob("*.aotx"))
    assert len(blobs) == 1
    assert (1, 16, 64) in v._full_cache
    v2 = tiny_voice(seed=11)
    with caplog.at_level(logging.WARNING, logger="sonata"):
        t0 = time.monotonic()
        v2.warm_shape((1, 16, 64))
        load_s = time.monotonic() - t0
    assert (1, 16, 64) in v2._full_cache
    # the timing bar is a proxy for "deserialized, not re-traced" — it
    # only means anything when XLA actually accepted the blob.  On this
    # CPU backend the import can refuse an in-process roundtrip with
    # "Symbols not found" DEPENDING ON PROCESS HISTORY (how many other
    # executables the suite compiled first), in which case warm_shape's
    # documented fallback re-jits via the persistent compile cache and
    # wall time measures that instead.  Correctness (the numerics pin
    # below) holds on either path.
    fell_back = any("falling back to jit warmup" in r.getMessage()
                    for r in caplog.records)
    if not fell_back:
        assert load_s < 2.0  # deserialize, not retrace+recompile
    p = list(v.phonemize_text("Hi."))[0]
    a1 = v.speak_batch([p])[0]
    a2 = v2.speak_batch([p])[0]
    assert np.allclose(a1.samples.data, a2.samples.data)


def test_warm_shape_aot_disabled_falls_back_to_jit(tmp_path, monkeypatch):
    monkeypatch.setenv("SONATA_AOT_CACHE", "off")
    from sonata_tpu.utils.jax_cache import aot_cache_dir

    assert aot_cache_dir() is None
    v = tiny_voice(seed=12)
    v.warm_shape((1, 16, 64))  # plain jit warm, no blobs anywhere
    assert (1, 16, 64) in v._full_cache


def test_aot_cache_dir_override_and_default(tmp_path, monkeypatch):
    from sonata_tpu.utils.jax_cache import aot_cache_dir

    override = tmp_path / "my_aot"
    monkeypatch.setenv("SONATA_AOT_CACHE", str(override))
    assert aot_cache_dir() == str(override)
    assert override.is_dir()
    monkeypatch.delenv("SONATA_AOT_CACHE")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    d = aot_cache_dir()
    assert d == str(tmp_path / "jc" / "aot")


def test_aot_corrupt_blob_falls_back(tmp_path, monkeypatch):
    """A truncated/corrupt blob must not fail the warmup — warm_shape
    falls back to the jit path and still makes the shape hot."""
    monkeypatch.setenv("SONATA_AOT_CACHE", str(tmp_path / "aot"))
    v = tiny_voice(seed=13)
    key = v._aot_key((1, 16, 64))
    aot = tmp_path / "aot"
    aot.mkdir()
    (aot / f"{key}.aotx").write_bytes(b"not a pickle")
    v.warm_shape((1, 16, 64))
    assert (1, 16, 64) in v._full_cache


def test_scaled_dispatch_cold_is_not_a_coverage_regression():
    """A request with a non-default length scale lands outside the
    lattice's promise: its cold compile is expected work, not an alarm."""
    scope = Scope(dump_dir=None)
    base = {"compile": "cold", "padding_ratio": 0.0, "batch_bucket": 1,
            "text_bucket": 16, "frame_bucket": 64, "voice": "v"}
    scope.mark_warmup_complete()
    scope.note_dispatch(0.1, {**base, "scaled": True})
    assert scope.runtime_cold_compiles_total() == 0
    scope.note_dispatch(0.1, dict(base))  # default scales: still armed
    assert scope.runtime_cold_compiles_total() == 1
    scope.close()


def test_lattice_beyond_table_frame_estimates_keep_range_coverage():
    """An estimated top bucket past FRAME_BUCKETS (bucket_for returns
    top-bucket multiples there) must not silently skip the reachable
    in-table run: the range clamps to the table top."""
    v = tiny_voice(seed=7)
    sc = v.get_fallback_synthesis_config()
    sc.length_scale = 30.0  # estimates blow past the 4096 table top
    v.set_fallback_synthesis_config(sc)
    shapes = v.lattice_shapes("minimal")
    by_text: dict = {}
    for _b, t, f in shapes:
        by_text.setdefault(t, set()).add(f)
    top = FRAME_BUCKETS[-1]
    saw_beyond = False
    for t, fs in by_text.items():
        beyond = {f for f in fs if f not in FRAME_BUCKETS}
        in_table = sorted(f for f in fs if f in FRAME_BUCKETS)
        if beyond and in_table:
            saw_beyond = True
            # the in-table run reaches the table top — no silent gap
            # between the warmed range and the beyond-table estimate
            assert in_table[-1] == top, (t, fs)
            idx = [FRAME_BUCKETS.index(f) for f in in_table]
            assert idx == list(range(idx[0], idx[-1] + 1)), (t, fs)
    assert saw_beyond  # the scenario actually triggered
