"""The parts of a cell that are files found by name.

A configuration names, by paths from the root of the repository, the files
that hold what is particular to its architecture:

- ``writer``: the voice's files and what the harness has to know of the voice,
- ``reference``: the plain reference, read by the comparison alone,
- ``check``: the comparison that decides ``correct``.

A key that is absent means the VITS file of ``DEFAULTS``, the one spot that
names them.  A metric's reader is ``<path>/metrics/<metric>.py`` and a
configuration's limits ``<path>/reference/limits/<config>.json``, looked
for under each directory of the benchmark's ``paths``.  Every module is
loaded from its path with ``importlib``, so a file under another directory
of ``paths`` is as good as one here.  Nothing here imports jax.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

DEFAULTS = {"writer": "perfbench/harness/voicegen.py",
            "reference": "perfbench/reference/vits_ref.py",
            "check": "perfbench/reference/vits_check.py"}


class PartError(RuntimeError):
    pass


def load_file(file: Path):
    """The module in ``file``, loaded once a process under a name made
    from its path."""
    file = Path(file).resolve()
    name = "perfbench_part_" + re.sub(r"\W", "_", str(file))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, file)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


def load(root: Path, paths: list, config: dict, key: str):
    """The module a configuration names under ``key``."""
    named = config.get(key, DEFAULTS[key])
    file = (Path(root) / named).resolve()
    inside = [Path(root, p).resolve() for p in paths]
    if not any(d in file.parents for d in inside):
        raise PartError(f"the configuration's {key!r}, {named!r}, lies "
                        f"under none of the benchmark's paths {paths}")
    if not file.is_file():
        raise PartError(f"no file {named!r} for the configuration's {key!r}")
    return load_file(file)


def find(root: Path, paths: list, *relative: str):
    """``<path>/<relative>`` under the first directory of ``paths`` that
    has it, or ``None``."""
    for p in paths:
        file = Path(root, p, *relative)
        if file.exists():
            return file
    return None


def load_reader(root: Path, paths: list, name: str):
    file = find(root, paths, "metrics", f"{name}.py")
    if file is None:
        raise PartError(f"no reader file for the metric {name!r}")
    return load_file(file).read


def load_limits(root: Path, paths: list, config_name: str) -> dict:
    """``{name: limit}`` of the numbers compared: ``limits.json``'s
    ``default``, its entry for the configuration over that, and the
    configuration's own file over both; ``null`` takes a number out."""
    limits: dict = {}
    shared = find(root, paths, "reference", "limits.json")
    if shared is not None:
        entries = json.loads(shared.read_text())
        limits.update(entries.get("default", {}))
        limits.update(entries.get(config_name, {}))
    own = find(root, paths, "reference", "limits", f"{config_name}.json")
    if own is not None:
        limits.update(json.loads(own.read_text()))
    return {k: v for k, v in limits.items() if v is not None}
