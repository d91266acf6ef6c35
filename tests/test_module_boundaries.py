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


def names_made_in(relative: str) -> set[str]:
    """Every name ``sonata_tpu/<relative>`` defines or assigns, at any
    depth (an attribute of ``self`` too)."""
    tree = ast.parse((PACKAGE / relative).read_text())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            found.add(node.name)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for target in targets:
                found.update(n.id if isinstance(n, ast.Name) else n.attr
                             for n in ast.walk(target)
                             if isinstance(n, (ast.Name, ast.Attribute)))
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
    # what the backbones share is no backbone's: none reaches into the
    # first one's module (PR 47)
    ("models/sdar.py", "sonata_tpu.models.lfm2", False),
    ("models/nemotron_h.py", "sonata_tpu.models.lfm2", False),
    ("models/pangu_moe.py", "sonata_tpu.models.lfm2", False),
    ("models/laguna.py", "sonata_tpu.models.lfm2", False),
    # the shared layers and the adapters' base stand below the voice and
    # the loop
    ("models/unit_layers.py", "sonata_tpu.models.unit_voice", False),
    ("models/unit_layers.py", "sonata_tpu.synth", False),
    ("models/unit_backbone.py", "sonata_tpu.models.unit_voice", False),
    ("models/unit_backbone.py", "sonata_tpu.synth", False),
    # the loop is a scheduler: it reads an engine's description and
    # imports no model
    ("synth/steploop.py", "sonata_tpu.models", False),
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
    assert name not in names_made_in("models/piper.py")


#: what left ``lfm2.py`` for ``unit_layers.py`` (PR 47)
LEFT_LFM2 = (
    "UnitIds", "BF16", "F32", "Params", "mm", "rms_norm", "apply_rope",
    "_qkv", "block_mask", "attn_op_seq", "attn_op_step", "swiglu",
    "dense_ffn", "route", "_expert_act", "pad_experts", "held_rows",
    "_held_experts", "moe_ffn", "expert_matmul", "_head", "allowed_ids",
    "_scaled_and_noisy", "sample", "_pick", "choose", "step_key", "join",
    "advance", "advance_and_join")
#: what left ``unit_voice.py`` for ``unit_backbone.py`` and the backbones'
#: modules, and what the voice lost with its copies and pass-throughs
LEFT_UNIT_VOICE = (
    "Backbone", "Description", "RowPlan", "TokenRows",
    "token_step_programs", "routes_of", "places_fetched", "LAYERS_ONCE",
    "_layers_once_here", "Lfm2Backbone", "SdarBackbone", "NemotronBackbone",
    "PanguBackbone", "LagunaBackbone", "ssm_layers", "ssm_state_bytes",
    "latent_layers", "_latent_chunk", "mla_form", "full_layers",
    "window_layers", "window", "_kv_places", "latent_cache_bytes",
    "latent_places", "kv_places_fetched", "kv_cache_bytes",
    "cache_resident_bytes")
LEFT = {"lfm2": LEFT_LFM2, "unit_voice": LEFT_UNIT_VOICE}


@pytest.mark.parametrize("module, name", [
    (f"models/{module}.py", name)
    for module, names in LEFT.items() for name in names])
def test_what_left_a_unit_voices_modules_left_no_alias_behind(module, name):
    assert name not in names_made_in(module)


def test_nobody_names_what_moved_by_the_module_it_left():
    """Callers, tests and tools name the module a thing lives in: no
    ``lfm2.moe_ffn``, no ``from ...unit_voice import RowPlan`` (what a
    module imports for its own use is not what it offers)."""
    root = PACKAGE.parent
    stale = []
    for path in [*PACKAGE.rglob("*.py"), *(root / "tools").rglob("*.py"),
                 *(p for p in (root / "tests").rglob("*.py")
                   if "perfbench" not in p.parts)]:
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name) \
                    and node.attr in LEFT.get(node.value.id, ()):
                stale.append((path.name, node.lineno, node.attr))
            elif isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.split(".")[-1] in LEFT:
                stale += [(path.name, node.lineno, alias.name)
                          for alias in node.names if alias.name
                          in LEFT[node.module.split(".")[-1]]]
    assert not stale


def test_a_backbones_pieces_are_the_shared_modules_own():
    """What ``lfm2``'s programs call of the shared layers it imports by
    name, as every other backbone does: each such name is defined in
    ``unit_layers`` and nowhere else under ``models/``."""
    shared = names_made_in("models/unit_layers.py")
    for module in ("lfm2", "sdar", "nemotron_h", "pangu_moe", "laguna"):
        tree = ast.parse((PACKAGE / f"models/{module}.py").read_text())
        defined = {n.name for n in tree.body
                   if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
        taken = {alias.name for n in tree.body
                 if isinstance(n, ast.ImportFrom)
                 and n.module == "unit_layers" for alias in n.names}
        assert taken and taken <= shared and not taken & defined, module


def test_no_backbones_adapter_subclasses_anothers_and_the_voice_has_none():
    """Every ``*Backbone`` stands on ``unit_backbone``'s ``Backbone`` or
    ``TokenRows``, in its backbone's module; ``unit_voice.py`` defines
    none and names each in one registry line."""
    bases = {}
    for module in ("lfm2", "sdar", "nemotron_h", "pangu_moe", "laguna",
                   "unit_voice"):
        tree = ast.parse((PACKAGE / f"models/{module}.py").read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) \
                    and node.name.endswith("Backbone"):
                bases[module, node.name] = [ast.unparse(b)
                                            for b in node.bases]
    assert sorted(module for module, _ in bases) == [
        "laguna", "lfm2", "nemotron_h", "pangu_moe", "sdar"]
    assert all(b in (["Backbone"], ["TokenRows"]) for b in bases.values()), \
        bases


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
