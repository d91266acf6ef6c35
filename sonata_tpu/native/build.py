"""Build-and-load machinery for the first-party C++ components.

Compiles ``src/*.cpp`` into shared libraries next to this file on first use
(equivalent to the reference's build.rs + cc/cmake static builds,
``crates/audio/sonic-sys/build.rs:9-12``), caches by source mtime, and
exposes ctypes handles.  Failures are non-fatal: callers fall back to the
numpy implementations.
"""

from __future__ import annotations

import ctypes
import logging
import subprocess
import threading
from pathlib import Path
from typing import Optional

log = logging.getLogger("sonata.native")

_DIR = Path(__file__).resolve().parent
_LOCK = threading.Lock()
_CACHE: dict[str, Optional[ctypes.CDLL]] = {}


def native_dir() -> Path:
    return _DIR


def _python_flags() -> tuple[list[str], list[str]]:
    """(cflags, ldflags) for embedding CPython."""
    import sysconfig

    include = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    version = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_python_version()
    cflags = [f"-I{include}"]
    ldflags = [f"-L{libdir}", f"-lpython{version}"] if libdir else []
    return cflags, ldflags


def _build(name: str, *, embed_python: bool = False) -> Optional[Path]:
    src = _DIR / "src" / f"{name}.cpp"
    lib = _DIR / f"lib{name}.so"
    if not src.exists():
        return None
    # staleness check includes headers: an ABI struct edit in include/
    # must trigger a rebuild even if the .cpp is untouched
    dep_mtime = src.stat().st_mtime
    for header in (_DIR / "include").glob("*.h"):
        dep_mtime = max(dep_mtime, header.stat().st_mtime)
    if lib.exists() and lib.stat().st_mtime >= dep_mtime:
        return lib
    extra_c: list[str] = []
    extra_ld: list[str] = []
    if embed_python:
        extra_c, extra_ld = _python_flags()
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", *extra_c,
           "-o", str(lib), str(src), *extra_ld]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build of %s failed to run: %s", name, e)
        return None
    if proc.returncode != 0:
        log.warning("native build of %s failed:\n%s", name, proc.stderr[-2000:])
        return None
    log.info("native build of %s: compiled %s", name, lib)
    return lib


def _load(name: str, *, embed_python: bool = False) -> Optional[ctypes.CDLL]:
    with _LOCK:
        if name in _CACHE:
            return _CACHE[name]
        lib_path = _build(name, embed_python=embed_python)
        handle = None
        if lib_path is not None:
            # only the python-embedding library needs process-global
            # symbol visibility (to resolve libpython symbols)
            mode = ctypes.RTLD_GLOBAL if embed_python else ctypes.DEFAULT_MODE
            try:
                handle = ctypes.CDLL(str(lib_path), mode=mode)
            except OSError as e:
                # a wheel may ship a foreign-platform or stale binary:
                # rebuild from the vendored sources once, then give up to
                # the numpy fallback
                log.warning("cannot load %s (%s); rebuilding", lib_path, e)
                try:
                    lib_path.unlink()
                except OSError:
                    pass
                lib_path = _build(name, embed_python=embed_python)
                if lib_path is not None:
                    try:
                        handle = ctypes.CDLL(str(lib_path), mode=mode)
                    except OSError as e2:
                        log.warning("cannot load rebuilt %s: %s",
                                    lib_path, e2)
        if handle is not None:
            log.info("native library %s loaded", name)
        _CACHE[name] = handle
        return handle


def load_dsp_library() -> Optional[ctypes.CDLL]:
    """The prosody DSP library (rate/pitch/volume), or None."""
    lib = _load("sonata_dsp")
    if lib is not None and not hasattr(lib, "_sonata_configured"):
        lib.sonata_dsp_output_len.restype = ctypes.c_int64
        lib.sonata_dsp_output_len.argtypes = [ctypes.c_int64, ctypes.c_float,
                                              ctypes.c_float]
        lib.sonata_dsp_process.restype = ctypes.c_int64
        lib.sonata_dsp_process.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int,
            ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ]
        lib.sonata_dsp_version.restype = ctypes.c_char_p
        lib._sonata_configured = True
    return lib


def load_capi_library() -> Optional[ctypes.CDLL]:
    """The C ABI frontend (libsonata_tpu-equivalent), or None."""
    return _load("sonata_capi", embed_python=True)
