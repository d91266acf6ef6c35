"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip sharding tests run on a virtual mesh
(``--xla_force_host_platform_device_count=8``) so the suite is hermetic on
any machine; execution on a real TPU is ``chip_smoke.py``'s job (README
"Tests and benchmarks").  ``JAX_PLATFORMS`` and ``XLA_FLAGS`` are set before
the first jax import, which is when JAX reads them.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Bucket-lattice warmup (serving/warmup.py) defaults to "full" — right
# for production boots, tens of compiles too many for unit tests that
# merely need readiness to flip.  Dedicated lattice tests opt back in
# with monkeypatch.setenv("SONATA_WARMUP_LATTICE", ...).
os.environ.setdefault("SONATA_WARMUP_LATTICE", "off")

# Persistent executable cache: the suite's cost is almost entirely XLA
# compiles of the tiny test voices (hundreds of jit shapes across
# modules); caching them across runs cuts repeat suite time several-fold.
# Same resolution as every entry point: JAX_COMPILATION_CACHE_DIR if set,
# else the git-ignored <repo>/.jax_cache.
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

enable_persistent_compile_cache(0.5)

# ---------------------------------------------------------------------------
# Thread hygiene: fail any test that leaks a non-daemon thread past
# teardown (the PR 2/3 leak class: a scheduler/pool/server worker left
# running after the object that owned it was dropped).  Daemon threads
# are the repo's convention for owned workers and die with the process;
# a NON-daemon leak blocks interpreter exit and is always a bug in the
# test or the teardown path it exercises.  Opt out with
# ``@pytest.mark.allow_thread_leak`` for tests that intentionally hold
# threads across their boundary.
# ---------------------------------------------------------------------------

import threading
import time as _time

import pytest

#: shared process-lifetime infrastructure, never torn down per test
_THREAD_ALLOW_PREFIXES = (
    "sonata_synth",   # global synthesis pool (one per process by design)
)


@pytest.fixture(autouse=True)
def _thread_hygiene(request):
    if request.node.get_closest_marker("allow_thread_leak"):
        yield
        return
    before = {t.ident for t in threading.enumerate()}
    yield

    def leaked():
        return [t for t in threading.enumerate()
                if t.ident not in before and t.is_alive()
                and not t.daemon
                and not t.name.startswith(_THREAD_ALLOW_PREFIXES)]

    # small join grace: teardown paths legitimately take a moment to
    # wind their workers down
    deadline = _time.monotonic() + 2.0
    remaining = leaked()
    while remaining and _time.monotonic() < deadline:
        for t in remaining:
            t.join(timeout=0.2)
        remaining = leaked()
    if remaining:
        pytest.fail(
            "test leaked non-daemon thread(s) past teardown: "
            + ", ".join(sorted(t.name for t in remaining))
            + " — join them in the teardown path, or mark the test "
              "@pytest.mark.allow_thread_leak with a reason")


# Deterministic property tests: the driver runs pytest with -x, so a
# randomized hypothesis failure on a fresh seed would abort the whole
# suite; derandomize makes runs reproducible (new counterexamples are
# hunted explicitly, not by CI roulette).
try:
    from hypothesis import HealthCheck, settings

    settings.register_profile(
        "ci", derandomize=True, deadline=None,
        suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile("ci")
except ImportError:  # hypothesis optional outside property tests
    pass
