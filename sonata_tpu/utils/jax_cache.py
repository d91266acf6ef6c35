"""Persistent XLA compile cache setup, shared by every long-lived entry
point (gRPC server, CLI, benches, ``chip_smoke.py`` children).

The reference pays no compilation cost — ONNX Runtime sessions load in
milliseconds (``crates/sonata/models/piper/src/lib.rs:342-399``).  Here
every (batch, text, frame) shape is an XLA compile, so anything that boots
repeatedly must reuse compiled executables across processes.

One resolution for the whole tree: where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX's own handling of that variable is all there is and this module
sets no directory; where it is not, the cache lives at one fixed path
inside the checkout (``<repo>/.jax_cache``, git-ignored).  The path is part
of JAX's cache key, so a directory that moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
AOT_CACHE_ENV = "SONATA_AOT_CACHE"

#: the fixed in-checkout cache: the directory holding the ``sonata_tpu``
#: package, whatever the working directory of the process
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else the in-checkout path."""
    return os.environ.get(CACHE_DIR_ENV) or CHECKOUT_CACHE_DIR


def enable_persistent_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads the
    directory from the environment: nothing is set here.  Otherwise the
    in-checkout directory is created mode 0700 (a world-writable location
    could be pre-created and poisoned by another local user) and handed to
    ``jax.config``.  A directory that cannot be created raises: a process
    that silently runs uncached re-pays every compile on every boot.

    Every long-lived entry point starts here, before its first compile:
    this is also where the process's one compile listener is registered
    (:func:`sonata_tpu.serving.tracing.install_compile_listener`), so that
    what a start spends tracing, lowering, compiling and loading from this
    cache is counted program by program.
    """
    import jax

    from ..serving.tracing import install_compile_listener

    install_compile_listener()
    cache_dir = compile_cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        os.makedirs(cache_dir, mode=0o700, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return cache_dir


def aot_cache_dir() -> str | None:
    """Directory for serialized AOT executables (the warmup lattice's
    fast-boot layer), or None when disabled.

    JAX's own persistent cache skips the XLA compile on a cache hit but
    still re-traces and re-lowers every jitted shape on every boot.  The
    AOT layer serializes the *compiled executable* itself
    (``jax.experimental.serialize_executable``), so the next boot loads
    each shape with zero retracing.  ``SONATA_AOT_CACHE``: ``0``/``off``
    disables, a path overrides, unset defaults to ``<compile cache
    dir>/aot``.  Created mode 0700 — the blobs are pickles and the
    directory must be trusted like the XLA cache it sits inside.  A
    directory that cannot be created raises.
    """
    raw = (os.environ.get(AOT_CACHE_ENV) or "").strip()
    if raw.lower() in ("0", "off", "false", "no"):
        return None
    aot_dir = raw or os.path.join(compile_cache_dir(), "aot")
    os.makedirs(aot_dir, mode=0o700, exist_ok=True)
    return aot_dir
