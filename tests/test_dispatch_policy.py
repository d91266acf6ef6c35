"""Backend-adaptive dispatch policy (utils/dispatch_policy).

Pins the three layers of the ISSUE-1 contract:

- backend fast path: a CPU backend serves per-request (the r05 CPU
  streaming bench measured the coalescers at 2.6x the TTFB of
  per-request dispatch at 8 streams), while a TPU-class backend keeps
  the tuned coalescing defaults bit-for-bit;
- the env override (``SONATA_DISPATCH_POLICY``) beats the probe, so A/B
  benchmarking stays possible;
- the dispatch-scaling probe runs once per (backend, shape) and is
  cached; its result is visible in the observability counters.
"""

import pytest

from sonata_tpu.utils.buckets import canonical_dispatch_batch
from sonata_tpu.utils.dispatch_policy import (
    COALESCING_DEFAULTS,
    DispatchPolicy,
    ProbeResult,
    _clear_probe_cache,
    probe_dispatch_scaling,
    resolve_policy,
)
from voices import tiny_voice


def _fast_tpu_probe(calls=None):
    """A probe result shaped like a healthy local accelerator: near-flat
    batch scaling (8 items in 1.3x the batch-1 time)."""
    def fn(shape_key, backend=None):
        if calls is not None:
            calls.append((tuple(shape_key), backend))
        return ProbeResult(backend=backend or "tpu", n=8,
                           t1_ms=1.0, tn_ms=1.3)
    return fn


# ---------------------------------------------------------------------------
# resolution: backend fast path
# ---------------------------------------------------------------------------

def test_cpu_backend_gets_per_request_dispatch():
    """auto + CPU ⇒ the reference's thread-per-stream shape: batch 1,
    zero gather window, scheduler pass-through — and no probe paid."""
    def forbidden_probe(shape_key, backend=None):
        raise AssertionError("CPU fast path must not probe")

    p = resolve_policy(backend="cpu", env={}, probe_fn=forbidden_probe)
    assert p.coalesce is False
    assert p.stream_decode_kwargs() == {"max_batch": 1, "max_wait_ms": 0.0}
    assert p.stream_stage_kwargs() == {"max_batch": 1, "max_wait_ms": 0.0}
    assert p.scheduler_kwargs() == {"max_batch": 1, "max_wait_ms": 0.0}
    assert "cpu" in p.source


def test_tpu_backend_pins_current_coalescing_defaults():
    """auto + TPU-class backend ⇒ the exact pre-policy constants: the
    accelerator serving shape must not drift when policy code changes."""
    p = resolve_policy(backend="tpu", env={}, probe_fn=_fast_tpu_probe())
    assert p.coalesce is True
    assert p.stream_decode_kwargs() == {"max_batch": 8, "max_wait_ms": 2.0}
    assert p.stream_stage_kwargs() == {"max_batch": 8, "max_wait_ms": 8.0}
    assert p.scheduler_kwargs() == {"max_batch": 16, "max_wait_ms": 5.0}
    # and those are the module-level pinned defaults, bucket-canonical
    assert p.stream_decode_max_batch == canonical_dispatch_batch(
        COALESCING_DEFAULTS["stream_decode_max_batch"])


def test_serial_probe_result_disables_coalescing():
    """A non-CPU backend whose probe shows serial batch scaling (8 items
    ≈ 8x the time) also degrades to per-request dispatch."""
    def serial_probe(shape_key, backend=None):
        return ProbeResult(backend=backend, n=8, t1_ms=1.0, tn_ms=7.6)

    p = resolve_policy(backend="gpu", env={}, probe_fn=serial_probe)
    assert p.coalesce is False
    assert p.probe is not None and p.probe.batch_speedup < 1.5


def test_slow_dispatch_probe_stretches_gather_windows():
    """Per-dispatch overhead beyond the wait window stretches the gather
    windows — bounded — while a chip with negligible overhead keeps the
    exact defaults (previous test)."""
    def slow_dispatch_probe(shape_key, backend=None):
        # 40ms fixed dispatch overhead, cheap per-item scaling
        return ProbeResult(backend=backend, n=8, t1_ms=41.0, tn_ms=48.0)

    p = resolve_policy(backend="tpu", env={}, probe_fn=slow_dispatch_probe)
    assert p.coalesce is True
    assert p.stream_decode_max_wait_ms == 10.0   # clamped ceiling
    assert p.stream_stage_max_wait_ms == 25.0    # clamped ceiling
    assert p.stream_decode_max_batch == 8        # batch shape unchanged


def test_probe_failure_keeps_coalescing_defaults():
    def broken_probe(shape_key, backend=None):
        raise RuntimeError("device wedged")

    p = resolve_policy(backend="tpu", env={}, probe_fn=broken_probe)
    assert p.coalesce is True
    assert p.stream_decode_kwargs() == {"max_batch": 8, "max_wait_ms": 2.0}


# ---------------------------------------------------------------------------
# resolution: env overrides beat the probe
# ---------------------------------------------------------------------------

def test_dispatch_policy_env_beats_probe():
    calls = []
    # "off" forced on a TPU backend whose probe would say coalesce
    p = resolve_policy(backend="tpu",
                       env={"SONATA_DISPATCH_POLICY": "off"},
                       probe_fn=_fast_tpu_probe(calls))
    assert p.coalesce is False and not calls
    # "on" forced on a CPU backend the fast path would switch off
    p = resolve_policy(backend="cpu",
                       env={"SONATA_DISPATCH_POLICY": "on"},
                       probe_fn=_fast_tpu_probe(calls))
    assert p.coalesce is True and not calls
    assert p.stream_decode_kwargs() == {"max_batch": 8, "max_wait_ms": 2.0}


def test_invalid_policy_env_falls_back_to_auto():
    p = resolve_policy(backend="cpu",
                       env={"SONATA_DISPATCH_POLICY": "banana"},
                       probe_fn=_fast_tpu_probe())
    assert p.coalesce is False  # auto → cpu fast path


# ---------------------------------------------------------------------------
# probe caching
# ---------------------------------------------------------------------------

def test_probe_runs_once_and_is_cached():
    _clear_probe_cache()
    try:
        r1 = probe_dispatch_scaling((32, 256), reps=1)
        r2 = probe_dispatch_scaling((32, 256), reps=1)
        assert r1 is r2  # cache hit, not a re-measurement
        r3 = probe_dispatch_scaling((64, 256), reps=1)
        assert r3 is not r1  # distinct voice shape ⇒ distinct probe
        assert r1.t1_ms > 0 and r1.tn_ms > 0
        assert r1.per_dispatch_ms >= 0 and r1.per_item_ms >= 0
    finally:
        _clear_probe_cache()


def test_voice_policy_resolved_once(monkeypatch):
    """The voice property caches the resolved policy: env flips after
    first resolution don't change the serving shape mid-flight."""
    v = tiny_voice(seed=40)
    p1 = v.dispatch_policy
    monkeypatch.setenv("SONATA_DISPATCH_POLICY", "on")
    assert v.dispatch_policy is p1


# ---------------------------------------------------------------------------
# threading through the voice / coalescers / scheduler
# ---------------------------------------------------------------------------

def test_voice_on_cpu_backend_streams_per_request():
    v = tiny_voice(seed=41)
    try:
        assert v.dispatch_policy.coalesce is False  # suite runs on CPU
        chunks = list(v.stream_synthesis("həlˈoʊ wˈɜːld", 20, 3))
        assert chunks and all(len(c.samples) > 0 for c in chunks)
        assert v._stream_coalescer._max_batch == 1
        assert v._stage_coalescer._max_batch == 1
        assert v._stream_coalescer._max_wait == 0.0
    finally:
        v.close()


def test_env_override_reaches_coalescers(monkeypatch):
    monkeypatch.setenv("SONATA_DISPATCH_POLICY", "on")
    v = tiny_voice(seed=42)
    try:
        assert v.dispatch_policy.coalesce is True
        assert v._stream_decoder._max_batch == 8
        assert v._stream_stages._max_batch == 8
    finally:
        v.close()


def test_explicit_policy_injection_wins(monkeypatch):
    """A policy passed to __init__ is used verbatim — no env, no probe."""
    monkeypatch.setenv("SONATA_DISPATCH_POLICY", "off")
    from sonata_tpu.models import PiperVoice

    pol = DispatchPolicy(backend="test", coalesce=True, source="injected",
                         stream_decode_max_batch=4,
                         stream_decode_max_wait_ms=1.0)
    base = tiny_voice(seed=43)
    v = PiperVoice(base.config, base.params, dispatch_policy=pol)
    try:
        assert v.dispatch_policy is pol
        assert v._stream_decoder._max_batch == 4
    finally:
        v.close()
        base.close()


def test_batch_scheduler_defaults_from_voice_policy():
    from sonata_tpu.synth import BatchScheduler

    v = tiny_voice(seed=44)
    s = BatchScheduler(v)  # no explicit knobs
    try:
        # CPU backend ⇒ pass-through shape from the policy
        assert s._max_batch == 1 and s._max_wait == 0.0
    finally:
        s.shutdown()
        v.close()
    # explicit kwargs always win over the policy
    s = BatchScheduler(v, max_batch=8, max_wait_ms=200.0)
    try:
        assert s._max_batch == 8 and abs(s._max_wait - 0.2) < 1e-9
    finally:
        s.shutdown()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_dispatch_stats_report_counters_and_policy():
    v = tiny_voice(seed=45)
    try:
        for _ in v.stream_synthesis("wˈʌn tˈuː θɹˈiː", 20, 3):
            pass
        stats = v.dispatch_stats()
        pol = stats["policy"]
        assert pol["coalesce"] is False and pol["backend"] == "cpu"
        for stage in ("stream_decode", "stream_stage"):
            s = stats[stage]
            assert s["requests"] >= 1 and s["dispatches"] >= 1
            # per-request policy ⇒ ratio exactly 1.0 request/dispatch
            assert s["coalescing_ratio"] == 1.0
        # the synthesizer wrapper delegates the same view
        from sonata_tpu.synth import SpeechSynthesizer

        assert SpeechSynthesizer(v).dispatch_stats()["policy"] == pol
    finally:
        v.close()


def test_scheduler_reports_dispatch_counters():
    from sonata_tpu.synth import BatchScheduler

    v = tiny_voice(seed=46)
    s = BatchScheduler(v, max_batch=4, max_wait_ms=50.0)
    try:
        s.speak("tɛst wˈʌn")
        s.speak("tɛst tˈuː")
        assert s.stats["requests"] == 2
        assert 1 <= s.stats["dispatches"] <= 2
    finally:
        s.shutdown()
        v.close()


# ---------------------------------------------------------------------------
# donation gating
# ---------------------------------------------------------------------------

def test_window_decoder_not_donated_by_default():
    """No arg carries the donation annotation (the windows buffer can
    never alias the waveform), so the r05 'donated buffers were not
    usable' warning cannot fire."""
    import jax
    import jax.numpy as jnp

    v = tiny_voice(seed=47)
    try:
        fn = v._decode_windows_batch_fn(16, 2, False)
        lowered = fn.lower(v.params,
                           jnp.ones((2, 16, v.hp.inter_channels),
                                    jnp.float32))
        assert not any(i.donated
                       for i in jax.tree_util.tree_leaves(lowered.args_info))
    finally:
        v.close()
