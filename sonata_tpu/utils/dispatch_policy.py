"""Backend-adaptive dispatch policy: probe-driven coalescing defaults.

The stream coalescers and the continuous-batching scheduler were designed
for the TPU MXU, where a batched dispatch costs roughly the same wall
time as batch 1 — so funneling N concurrent requests into ONE padded
device program converts contention into throughput.  On a host-CPU
backend the same architecture *loses*: XLA:CPU executes the batch rows
essentially serially, the canonical-batch padding (b ∈ {1, max}) is real
compute, and the gather window is pure added latency (a round-5 run on a
2-vCPU host, recorded in CHANGES.md PR 1, had the coalescing config at
2.6x the TTFB of coalescing-off under 8 concurrent CPU streams).

This module makes the framework act on its own measurements instead of
hard-coded constants (the Orca/vLLM adaptive-batching lineage, PAPERS.md
"continuous batching"):

- :func:`probe_dispatch_scaling` — a one-time, process-cached probe per
  (backend, voice-shape): time a tiny jitted decode-like program at
  batch 1 vs batch N (compiles excluded) and split the cost into
  per-dispatch overhead vs per-item scaling.
- :func:`resolve_policy` — derive concrete knobs for both stream
  coalescers (``synth/stream_engines.py``), the :class:`~sonata_tpu.synth.
  scheduler.BatchScheduler`, and the canonical stream batch bucket
  (:mod:`.buckets`).  Fast path: ``jax.default_backend() == "cpu"`` →
  per-request dispatch, the reference's thread-per-stream serving shape
  (``grpc/src/main.rs:381-409``), with no probe paid.  TPU/GPU → the
  tuned coalescing defaults, with the probe refining the gather windows
  (the larger the per-dispatch overhead, the cheaper it is to wait a
  little longer and gather a fuller batch).

The env override always wins over the probe (A/B work must stay
possible): ``SONATA_DISPATCH_POLICY=auto|on|off``.  ``on``/``off`` force
the corresponding shape; ``auto`` (default) applies the backend fast
path + probe.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Callable, Optional

from .buckets import canonical_dispatch_batch

log = logging.getLogger("sonata.dispatch")

#: Tuned accelerator defaults — the exact constants the coalescers and
#: scheduler shipped with before the policy existed; unit-test-pinned so
#: the TPU serving shape cannot drift when the policy code changes.
COALESCING_DEFAULTS = {
    "stream_decode_max_batch": 8,
    "stream_decode_max_wait_ms": 2.0,
    "stream_stage_max_batch": 8,
    "stream_stage_max_wait_ms": 8.0,
    "scheduler_max_batch": 16,
    "scheduler_max_wait_ms": 5.0,
}

#: Below this measured parallel speedup at the probe batch, batching N
#: items into one dispatch costs about what N serial dispatches cost —
#: coalescing then buys nothing and its padding/gather-window overhead
#: makes it a net loss (the r05 CPU measurement).
MIN_BATCH_SPEEDUP = 1.5


@dataclass(frozen=True)
class ProbeResult:
    """One dispatch-scaling measurement on a backend.

    ``t1_ms``/``tn_ms``: best-of-reps wall time of the probe program at
    batch 1 and batch ``n``.  The linear split ``t(b) ≈ per_dispatch_ms
    + b * per_item_ms`` is what the policy consumes: ``batch_speedup =
    n * t1 / tn`` is the parallel efficiency of batching (n on an ideal
    MXU, →1.0 on a serial backend).
    """

    backend: str
    n: int
    t1_ms: float
    tn_ms: float

    @property
    def per_item_ms(self) -> float:
        return max((self.tn_ms - self.t1_ms) / max(self.n - 1, 1), 0.0)

    @property
    def per_dispatch_ms(self) -> float:
        return max(self.t1_ms - self.per_item_ms, 0.0)

    @property
    def batch_speedup(self) -> float:
        return self.n * self.t1_ms / max(self.tn_ms, 1e-9)

    def as_dict(self) -> dict:
        d = asdict(self)
        d.update(per_item_ms=round(self.per_item_ms, 4),
                 per_dispatch_ms=round(self.per_dispatch_ms, 4),
                 batch_speedup=round(self.batch_speedup, 3))
        return d


@dataclass(frozen=True)
class DispatchPolicy:
    """Concrete dispatch knobs for one (backend, voice-shape).

    ``coalesce`` is the headline decision; the per-subsystem knobs are
    what :class:`~sonata_tpu.models.piper.PiperVoice`, the stream
    engines (``synth/stream_engines.py``), the batch scheduler and the
    warm-up lattice (``models/shape_plan.py``) actually consume.
    ``source`` records *why* (env override / backend fast path / probe)
    so the decision is visible in logs and bench artifacts.
    """

    backend: str
    coalesce: bool
    source: str
    stream_decode_max_batch: int = 8
    stream_decode_max_wait_ms: float = 2.0
    stream_stage_max_batch: int = 8
    stream_stage_max_wait_ms: float = 8.0
    scheduler_max_batch: int = 16
    scheduler_max_wait_ms: float = 5.0
    probe: Optional[ProbeResult] = field(default=None, compare=False)

    # -- consumer views --------------------------------------------------
    def stream_decode_kwargs(self) -> dict:
        return {"max_batch": self.stream_decode_max_batch,
                "max_wait_ms": self.stream_decode_max_wait_ms}

    def stream_stage_kwargs(self) -> dict:
        return {"max_batch": self.stream_stage_max_batch,
                "max_wait_ms": self.stream_stage_max_wait_ms}

    def scheduler_kwargs(self) -> dict:
        return {"max_batch": self.scheduler_max_batch,
                "max_wait_ms": self.scheduler_max_wait_ms}

    def as_dict(self) -> dict:
        """Observability view (logs, bench artifacts)."""
        d = asdict(self)
        d["probe"] = self.probe.as_dict() if self.probe else None
        return d

    def describe(self) -> str:
        """One log line: the decision, where it came from, and the
        probe's measurement when one ran."""
        probe = ""
        if self.probe is not None:
            probe = (f" probe(n={self.probe.n} "
                     f"t1={self.probe.t1_ms:.3f}ms "
                     f"tn={self.probe.tn_ms:.3f}ms)")
        return (f"dispatch policy [{self.backend}]: "
                f"coalesce={'on' if self.coalesce else 'off'} "
                f"(decode b{self.stream_decode_max_batch}/"
                f"{self.stream_decode_max_wait_ms:g}ms, "
                f"stage b{self.stream_stage_max_batch}/"
                f"{self.stream_stage_max_wait_ms:g}ms, "
                f"sched b{self.scheduler_max_batch}/"
                f"{self.scheduler_max_wait_ms:g}ms) via {self.source}"
                f"{probe}")


# ---------------------------------------------------------------------------
# probe
# ---------------------------------------------------------------------------

_PROBE_CACHE: dict = {}
_PROBE_LOCK = threading.Lock()


def _default_backend() -> str:
    import jax

    return jax.default_backend()


def _time_best(fn, args, reps: int) -> float:
    """Best-of-``reps`` blocking wall time of one jitted call, ms."""
    import jax

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1000.0


def probe_dispatch_scaling(shape_key: tuple = (), *, n: int = 8,
                           reps: int = 5,
                           backend: Optional[str] = None) -> ProbeResult:
    """Measure per-dispatch overhead vs per-item scaling, once per
    (backend, voice-shape, n); later calls return the cached result.

    The probe program is a tiny decode-shaped stack (a few matmul+tanh
    layers over [b, T, C]) — small enough that two XLA compiles cost well
    under a second on a 1-core host, large enough that a backend that
    parallelizes the batch dimension shows it.  ``shape_key``'s first
    element (the voice's latent channel count) sizes the probe's channel
    dimension, bounded, so distinct voice shapes measure distinct
    programs rather than caching N copies of one measurement.  Compiles
    and warmup are excluded from the timing; best-of-``reps`` suppresses
    scheduler noise on loaded hosts.
    """
    backend = backend or _default_backend()
    key = (backend, tuple(shape_key), n)
    with _PROBE_LOCK:
        cached = _PROBE_CACHE.get(key)
    if cached is not None:
        return cached

    import jax
    import jax.numpy as jnp

    T = 32
    C = 64
    if shape_key and isinstance(shape_key[0], int):
        C = max(16, min(int(shape_key[0]), 512))

    @jax.jit
    def tick(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    w = jnp.eye(C, dtype=jnp.float32) * 0.5
    x1 = jnp.ones((1, T, C), jnp.float32)
    xn = jnp.ones((n, T, C), jnp.float32)
    # warm both shapes (compile + first-run allocation excluded)
    jax.block_until_ready(tick(x1, w))
    jax.block_until_ready(tick(xn, w))
    result = ProbeResult(backend=backend, n=n,
                         t1_ms=_time_best(tick, (x1, w), reps),
                         tn_ms=_time_best(tick, (xn, w), reps))
    with _PROBE_LOCK:
        # first writer wins; a concurrent duplicate probe is harmless
        cached = _PROBE_CACHE.setdefault(key, result)
    log.debug("dispatch probe %s: t1=%.3fms tn=%.3fms speedup=%.2fx",
              key, cached.t1_ms, cached.tn_ms, cached.batch_speedup)
    return cached


def _clear_probe_cache() -> None:
    """Test hook."""
    with _PROBE_LOCK:
        _PROBE_CACHE.clear()


# ---------------------------------------------------------------------------
# policy resolution
# ---------------------------------------------------------------------------

def _per_request_policy(backend: str, source: str,
                        probe: Optional[ProbeResult] = None
                        ) -> DispatchPolicy:
    """The reference's thread-per-stream shape (grpc/src/main.rs:381-409):
    batch 1, zero gather window, scheduler pass-through."""
    return DispatchPolicy(
        backend=backend, coalesce=False, source=source, probe=probe,
        stream_decode_max_batch=1, stream_decode_max_wait_ms=0.0,
        stream_stage_max_batch=1, stream_stage_max_wait_ms=0.0,
        scheduler_max_batch=1, scheduler_max_wait_ms=0.0)


def _clamp(x: float, lo: float, hi: float) -> float:
    return max(lo, min(hi, x))


def _coalescing_policy(backend: str, source: str,
                       probe: Optional[ProbeResult] = None
                       ) -> DispatchPolicy:
    """The accelerator defaults; with a probe, the gather windows scale
    with measured per-dispatch overhead (the more a dispatch costs, the
    cheaper it is to wait a little longer for a fuller batch), floored
    at the pinned defaults so a chip with negligible per-dispatch
    overhead keeps the exact shipped constants."""
    d = dict(COALESCING_DEFAULTS)
    if probe is not None:
        ovh = probe.per_dispatch_ms
        d["stream_decode_max_wait_ms"] = _clamp(
            2.0 * ovh, d["stream_decode_max_wait_ms"], 10.0)
        d["stream_stage_max_wait_ms"] = _clamp(
            4.0 * ovh, d["stream_stage_max_wait_ms"], 25.0)
        d["scheduler_max_wait_ms"] = _clamp(
            2.0 * ovh, d["scheduler_max_wait_ms"], 15.0)
    # canonical-batch rule: the coalescers pad every multi-request group
    # to ONE batch size, which must be a compiled batch bucket so prewarm
    # and dispatch agree on the executable set
    for k in ("stream_decode_max_batch", "stream_stage_max_batch",
              "scheduler_max_batch"):
        d[k] = canonical_dispatch_batch(int(d[k]))
    return DispatchPolicy(backend=backend, coalesce=True, source=source,
                          probe=probe, **d)


def resolve_policy(shape_key: tuple = (), *,
                   backend: Optional[str] = None,
                   env: Optional[dict] = None,
                   probe_fn: Optional[Callable[..., ProbeResult]] = None
                   ) -> DispatchPolicy:
    """Resolve the dispatch policy for one voice.

    Precedence (each layer wins over everything below it):

    1. ``SONATA_DISPATCH_POLICY=on|off`` — forced shape, no probe.
    2. ``auto`` (default): backend fast path — CPU serves per-request
       without paying a probe; other backends run the cached
       :func:`probe_dispatch_scaling` and keep coalescing only if the
       measured batch speedup clears :data:`MIN_BATCH_SPEEDUP`.

    ``backend``, ``env`` and ``probe_fn`` exist for tests (mocked
    devices, counted probes); production callers pass nothing.
    """
    env = os.environ if env is None else env
    backend = backend or _default_backend()
    probe_fn = probe_fn or probe_dispatch_scaling

    mode = env.get("SONATA_DISPATCH_POLICY", "auto").lower()
    if mode not in ("auto", "on", "off"):
        log.warning("invalid SONATA_DISPATCH_POLICY=%r (use auto|on|off); "
                    "falling back to auto", mode)
        mode = "auto"
    if mode == "on":
        return _coalescing_policy(backend, "env:SONATA_DISPATCH_POLICY=on")
    if mode == "off":
        return _per_request_policy(backend, "env:SONATA_DISPATCH_POLICY=off")

    # -- auto ------------------------------------------------------------
    if backend == "cpu":
        # fast path: no probe.  XLA:CPU runs batch rows ~serially, so the
        # coalescers' padding + gather window are pure overhead (2.6x
        # TTFB loss at 8 streams on a 2-vCPU host, CHANGES.md PR 1).
        return _per_request_policy(backend, "auto:cpu-backend")
    try:
        probe = probe_fn(shape_key, backend=backend)
    except Exception as e:  # a broken probe must never block serving
        log.warning("dispatch probe failed (%s); keeping coalescing "
                    "defaults", e)
        return _coalescing_policy(backend, "auto:probe-failed")
    if probe.batch_speedup < MIN_BATCH_SPEEDUP:
        return _per_request_policy(
            backend, f"auto:probe-speedup-{probe.batch_speedup:.2f}x",
            probe=probe)
    return _coalescing_policy(
        backend, f"auto:probe-speedup-{probe.batch_speedup:.2f}x",
        probe=probe)
