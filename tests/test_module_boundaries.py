"""Which way the arrows point between the stock voice's modules (PR 30).

Read from the source, not from ``sys.modules``: an import made lazily
inside a function is allowed where the table says so, an import at module
level is not.
"""

import ast
import importlib.util
from pathlib import Path

import pytest

PACKAGE = Path(__file__).resolve().parent.parent / "sonata_tpu"


def imports_of(relative: str, *, module_level_only: bool) -> set[str]:
    """Absolute dotted names ``sonata_tpu/<relative>`` imports."""
    path = PACKAGE / relative
    package = ["sonata_tpu", *Path(relative).parent.parts]
    tree = ast.parse(path.read_text())
    nodes = ast.walk(tree)
    if module_level_only:
        def outside_functions(node):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    continue
                yield child
                yield from outside_functions(child)
        nodes = outside_functions(tree)
    found = set()
    for node in nodes:
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = (package[:len(package) - node.level + 1]
                    if node.level else [])
            stem = ".".join(base + ([node.module] if node.module else []))
            found.add(stem)
            found.update(f"{stem}.{alias.name}" for alias in node.names)
    return found


def reaches(imported: set[str], target: str) -> bool:
    return any(name == target or name.startswith(target + ".")
               for name in imported)


@pytest.mark.parametrize("module, forbidden, module_level_only", [
    # the shape decision reads ladders and a policy's numbers, nothing else
    ("models/shape_plan.py", "sonata_tpu.models.piper", False),
    ("models/shape_plan.py", "sonata_tpu.synth", False),
    ("models/shape_plan.py", "sonata_tpu.serving", False),
    # a second voice family does not reach into the first
    ("models/unit_voice.py", "sonata_tpu.models.piper", False),
    # the voice and its engines meet where an engine is built, lazily
    ("models/piper.py", "sonata_tpu.synth.stream_engines", True),
    ("synth/stream_engines.py", "sonata_tpu.models", True),
    # the voice owns no batching core
    ("models/piper.py", "sonata_tpu.synth.batching.BatchingCore", False),
    ("models/piper.py", "sonata_tpu.synth.batching.IterationLoop", False),
    ("models/piper.py", "sonata_tpu.synth.batching.WorkItem", False),
    # where the two borrowed helpers live now
    ("utils/transfer.py", "sonata_tpu.models", False),
    ("models/decode_opts.py", "sonata_tpu.models.piper", False),
    # a kernel knows shapes, not who calls it (PR 34)
    ("ops/grouped_matmul.py", "sonata_tpu.models", False),
    ("ops/grouped_matmul.py", "sonata_tpu.synth", False),
    ("ops/grouped_matmul.py", "sonata_tpu.serving", False),
])
def test_module_does_not_import(module, forbidden, module_level_only):
    imported = imports_of(module, module_level_only=module_level_only)
    assert not reaches(imported, forbidden), sorted(
        name for name in imported if reaches({name}, forbidden))


def test_the_voice_builds_its_engines_through_one_lazy_import():
    tree = ast.parse((PACKAGE / "models/piper.py").read_text())
    lazy = [node for node in ast.walk(tree)
            if isinstance(node, ast.ImportFrom)
            and any(alias.name == "stream_engines" for alias in node.names)]
    assert len(lazy) == 1


@pytest.mark.parametrize("name", [
    "_frames_per_id", "_fpi_observed", "_fpi_lock", "_frame_budget",
    "_estimate_frame_bucket", "_observe_frames", "_plan_dispatch_groups",
    "_iteration_lattice_shapes", "_decode_quantize", "_prefetch_to_host",
    "_StreamDecodeCoalescer", "_IterationStreamDecoder",
    "_StreamStageCoalescer", "_drain_pending_futures"])
def test_what_moved_out_of_the_voice_left_no_alias_behind(name):
    tree = ast.parse((PACKAGE / "models/piper.py").read_text())
    defined = {node.name for node in ast.walk(tree)
               if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
    assigned = {target.id if isinstance(target, ast.Name) else target.attr
                for node in ast.walk(tree) if isinstance(node, ast.Assign)
                for target in node.targets
                if isinstance(target, (ast.Name, ast.Attribute))}
    assert name not in defined | assigned


def test_the_ops_package_holds_the_two_kernels():
    """PR 30 took the package out with its last kernel; PR 34 brought it
    back for the expert products' grouped matmul, PR 37 put the slots'
    keys and values and their reader beside it, and nothing else."""
    assert sorted(p.name for p in (PACKAGE / "ops").glob("*.py")) == [
        "__init__.py", "grouped_matmul.py", "slot_attention.py"]
    assert importlib.util.find_spec(f"{PACKAGE.name}.ops") is not None
    # the package re-exports nothing: a function named as its module
    # would hide the module
    tree = ast.parse((PACKAGE / "ops/__init__.py").read_text())
    assert not [n for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))]
