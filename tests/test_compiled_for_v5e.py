"""What the TPU's compiler makes of this repo's kernels and of the unit
voices' step programs, compiled here for the chip the cells run on (a v5e
that is described, not attached: nothing runs).  All such tests live in
this one file: a process that has described the topology holds the TPU's
library, and a second file could land on another worker.

- both kernels at the step programs' real widths: alignment and VMEM, which
  interpret mode cannot show;
- each backbone's step program at its cell's size: it may hold no ``copy``
  of a whole key, value or routes buffer (until PR 37 XLA re-laid every
  buffer twice a step), and both kernels are in it, the reader lowered
  once a geometry and not once a layer;
- the step that carries an arrival (``lfm2_step_admit``,
  ``nemotron_step_admit``, ``pangu_step_admit``, ``laguna_step_admit``,
  ``gigachat_step_admit``) likewise: a step's scatter and a prompt's slice
  land on one donated buffer in one program, which is where a copy could
  come back, and its expert products over both kinds of row are the
  kernel's."""

import functools
import importlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import lfm2gen
from sonata_tpu.models import unit_backbone, unit_voice
from test_grouped_matmul import STEP_SHAPES
from test_slot_attention import GEOMETRIES
from tools.profile_start import inner_jaxprs

sa = importlib.import_module("sonata_tpu.ops.slot_attention")
gm = importlib.import_module("sonata_tpu.ops.grouped_matmul")
ROOT = Path(__file__).resolve().parent.parent
BF16, F32 = jnp.bfloat16, jnp.float32


#: the cells' step programs: configuration, writer, slots
CELLS = {
    "lfm2_step": ("perfbench/configs/lfm2/lfm2-24b-a2b.json", "lfm2gen", 64),
    "sdar_pass": ("perfbench/configs/sdar/sdar-30b-a3b.json", "sdargen", 64),
    "nemotron_step": (
        "perfbench/configs/nemotron/nemotron-3-nano-30b-a3b.json",
        "nemotrongen", 256),
    "pangu_step": ("perfbench/configs/pangu/openpangu-ultra-moe-718b.json",
                   "pangugen", 256),
    "laguna_step": ("perfbench/configs/laguna/laguna-xs.2.json", "lagunagen",
                    256),
    "gigachat_step": ("perfbench/configs/gigachat/gigachat3.5-432b-a28b.json",
                      "gigachatgen", 256),
}
POSITIONS = 1024
#: the two readers of ``laguna_step``: slots, and the places of a whole
#: cache and of a ring
READER_SHAPES = {"laguna_full": (256, 1024), "laguna_ring": (256, 512)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def spec_on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("name", sorted(STEP_SHAPES))
def test_the_grouped_matmul_compiles_for_a_v5e_at_the_step_shapes(
        one_chip, no_compile_cache, name):
    """The expert products' kernel with the rule's tiles (alignment, VMEM)
    at the real widths."""
    rows, groups, k, n = STEP_SHAPES[name]
    tiles = gm.tile_rule(rows, groups, k, n, BF16)
    spec = spec_on(one_chip)
    compiled = jax.jit(functools.partial(
        gm.grouped_matmul_kernel, tiles=tiles)).lower(
        spec((rows, k), BF16), spec((groups, k, n), BF16),
        spec((groups,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_the_slot_attention_compiles_for_a_v5e_at_the_step_shapes(
        one_chip, no_compile_cache, name):
    """The attention kernel with the rule's chunk at the real widths: the
    buffers handed over where they lie, a slot's keys and values copied by
    the kernel itself up to its length (the copies, their semaphores and
    the trip count from ``upto``: what interpret mode cannot refuse)."""
    kv, g, d, b = GEOMETRIES[name]
    slots, places = READER_SHAPES.get(name) or (CELLS[name][2], POSITIONS)
    tiles = sa.tile_rule(places, kv, g, d, b)
    spec = spec_on(one_chip)
    buf = spec(sa.stored_shape(slots, places, kv, d), BF16)
    compiled = jax.jit(functools.partial(
        sa.slot_attention_kernel, tiles=tiles)).lower(
        spec((slots, b, kv, g, d), F32), buf, buf,
        spec((slots,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_latent_reader_compiles_for_a_v5e_at_the_step_shape(
        one_chip, no_compile_cache):
    """The latent reader's kernel with the rule's chunk at the real widths:
    128 query heads on one row of 576 values in 640 lanes, the values its
    first 512; the buffer handed over where it lies, a slot's places copied
    by the kernel itself up to its length (the copies, their semaphores
    and the trip count from ``upto``: what interpret mode cannot refuse)."""
    slots, heads, width, values = 256, 128, 576, 512
    tiles = sa.latent_tile_rule(POSITIONS, heads, width, values, 1)
    assert tiles == sa.Tiles(128)
    spec = spec_on(one_chip)
    compiled = jax.jit(functools.partial(
        sa.latent_attention_kernel, values=values, scale=192 ** -0.5,
        tiles=tiles)).lower(
        spec((slots, 1, heads, width), F32),
        spec(sa.stored_shape(slots, POSITIONS, 1, width), BF16),
        spec((slots,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def step_shapes(name: str, sharding):
    """The backbone of a cell and the shapes of its step program's
    arguments at the cell's real size: no array is made."""
    path, writer, slots = CELLS[name]
    config = json.loads((ROOT / path).read_text())
    writer = importlib.import_module(f"perfbench.harness.{writer}")
    bb = writer.backbone(config)
    backbone = unit_voice.make_backbone(bb, config["voice"]["units"])

    def layer(i: int):
        prefix = f"layers.{i}."
        raw = lfm2gen.nest({
            s[0][len(prefix):]: jax.ShapeDtypeStruct(s[1], getattr(jnp, s[2]))
            for s in writer.layer_specs(bb, i)})
        return jax.eval_shape(backbone.pack_layer, raw)

    cfg = backbone.cfg
    embed = jax.ShapeDtypeStruct((cfg.vocab_size, cfg.hidden_size), BF16)
    params = {"embed": embed,
              "norm_f": jax.ShapeDtypeStruct((cfg.hidden_size,), F32),
              "layers": [layer(i) for i in range(backbone.layers)]}
    if not cfg.tie_word_embeddings:
        params["head"] = embed
    cache = jax.eval_shape(lambda: backbone.new_cache(slots, POSITIONS))
    args = (params, cache, jax.ShapeDtypeStruct((slots,), jnp.bool_),
            jax.ShapeDtypeStruct((slots,), F32),
            jax.ShapeDtypeStruct((), jnp.int32))
    return backbone, cache, jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)


def whole_buffer_copies(hlo: str, elements: int,
                        types: str = "bf16|s8|u8") -> list:
    """The ``copy`` operations of an optimised module whose result holds
    at least ``elements`` elements of ``types`` (bfloat16 or a byte: what a
    step writes per slot and place)."""
    found = []
    for line in hlo.splitlines():
        m = re.search(rf"= ({types})\[([\d,]+)\]\S* copy\(", line)
        if m and np.prod([int(n) for n in m.group(2).split(",")]) >= elements:
            found.append(line.strip()[:160])
    return found


def test_the_copies_of_the_layout_before_are_found():
    """Two lines of ``lfm2_step`` and one of ``sdar_pass`` as the parent
    compiled them for a v5e: the cache re-laid for the scatter and for the
    einsum, and the routes' record."""
    parent = """
  %copy.292 = bf16[64,1024,8,64]{3,2,1,0:T(8,128)(2,1)} copy(%custom-call.202), sharding={replicated}
  %copy.370 = s8[64,1024,8,4]{1,3,2,0:T(4,128)(4,1)} copy(%fusion.84), backend_config={}
  ROOT %copy.313 = bf16[64,1024,4,128,1]{3,1,4,2,0:T(8,128)(2,1)} copy(%param_0.636), metadata={}
  %copy.162 = bf16[64,4,4,8,128]{4,3,2,1,0:T(8,128)(2,1)S(1)} copy(%fusion.301), metadata={}
  %fusion.74 = bf16[64,1024,8,64]{3,2,1,0:T(8,128)(2,1)} fusion(%copy.292, %fusion.352)
"""
    assert len(whole_buffer_copies(parent, 64 * 1024 * 32)) == 3


def logits_sized(hlo: str, elements: int, types: str = "f32") -> tuple:
    """Of an optimised module's entry computation: the arrays of
    ``elements`` elements of ``types`` (float32: the logits') its
    operations write, as ``(operation, kind)`` (a fusion that writes two
    stands twice; parameters and what only names an array again aside),
    and the operations that read one."""
    entry = hlo[hlo.index("\nENTRY "):]
    arrays, made, read = set(), [], []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)", line)
        if not m:
            continue
        name, result, kind, rest = m.groups()
        operands = re.findall(r"%([\w.-]+)", re.split(
            r"\), |, calls=|, metadata=", rest)[0])
        n = sum(int(np.prod([int(d) for d in dims.split(",")])) == elements
                for dims in re.findall(rf"(?:{types})\[([\d,]+)\]", result))
        if n:
            arrays.add(name)
        if kind in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            continue
        made += [(name, kind)] * n
        if arrays.intersection(operands):
            read.append(name)
    return made, read


def test_the_logits_the_parent_wrote_twice_are_found():
    """Lines of ``sdar_pass`` as the parent compiled it for a v5e: the
    head's product (with ``log_softmax``'s maximum beside it), the two
    arg-max fusions and the sum that read it, ``log_softmax`` written out
    whole for a gather, and the copy to ``[64, 4, V]`` handed out."""
    parent = """
ENTRY %main.146 (params__embed__.1: bf16[151936,2048]) -> (f32[64,4,151936]) {
  %fusion.138 = (f32[256]{0:T(256)S(1)}, f32[256,151936]{1,0:T(8,128)}) fusion(%copy-done.118, %params__head__.1), kind=kOutput, calls=%fused_computation.163, metadata={op_name="jit(sdar_pass)/head/dot_general"}
  %get-tuple-element.585 = f32[256,151936]{1,0:T(8,128)} get-tuple-element(%fusion.138), index=1
  %iota_reduce_fusion.1 = (bf16[256]{0:T(256)(128)(2,1)}, s32[256]{0:T(256)S(1)}) fusion(%get-tuple-element.585, %copy-done.119), kind=kLoop, calls=%fused_computation.140
  %exponential_reduce_fusion = f32[256]{0:T(256)S(1)} fusion(%get-tuple-element.585, %get-tuple-element.584), kind=kLoop, calls=%fused_computation.160
  %iota_reduce_fusion = (bf16[256]{0:T(256)(128)(2,1)}, s32[256]{0:T(256)S(1)}) fusion(%get-tuple-element.585, %copy-done.58), kind=kLoop, calls=%fused_computation.139
  %subtract_subtract_fusion = f32[256,151936]{1,0:T(8,128)} fusion(%get-tuple-element.585, %get-tuple-element.584, %log.2), kind=kLoop, calls=%fused_computation.161
  %fusion.57 = f32[256]{0:T(256)S(1)} fusion(%subtract_subtract_fusion, %select_n.300), kind=kLoop, calls=%fused_computation.57
  %reshape.162 = f32[64,4,151936]{2,1,0:T(4,128)} reshape(%get-tuple-element.585), metadata={op_name="jit(sdar_pass)/reshape"}
  ROOT %tuple.274 = (f32[64,4,151936]{2,1,0:T(4,128)}) tuple(%reshape.162)
}
"""
    made, read = logits_sized(parent, 256 * 151936)
    assert made == [("fusion.138", "fusion"),
                    ("subtract_subtract_fusion", "fusion"),
                    ("reshape.162", "reshape")]
    assert read == ["iota_reduce_fusion.1", "exponential_reduce_fusion",
                    "iota_reduce_fusion", "subtract_subtract_fusion",
                    "fusion.57", "reshape.162"]


#: what a program's code may weigh (``generated_code_size_in_bytes``)
CODE_MB = {"step": 24, "step_admit": 36}
#: what reads the slots' cache in each step program
READERS = {name: "latent_attention" if name in ("pangu_step",
                                               "gigachat_step")
           else "slot_attention" for name in CELLS}
#: the traces and lowerings of its reader a step program holds: one a
#: geometry (a whole cache under 6 query heads a key head, a ring of 512
#: places under 8)
LOWERINGS = {"laguna_step": 2}
#: the layers of each that read the slots' cache
READS = {"lfm2_step": 2, "sdar_pass": 6, "nemotron_step": 1, "pangu_step": 7,
         "laguna_step": 8, "gigachat_step": 1}


def reader_calls(jaxpr, name: str) -> list:
    """The jaxprs under the equations that call the jitted function
    ``name``, anywhere in ``jaxpr``: one for each call."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") == name:
            found.append(eqn.params["jaxpr"])
        else:
            for inner in inner_jaxprs(eqn):
                found += reader_calls(inner, name)
    return found


def lowered_once_a_geometry(program, args, name: str):
    """``program`` traced and lowered for ``args``: every layer's reader is
    a call of the kernel's jitted function, the layers of one geometry
    share one trace of it (one jaxpr object) and the module holds one
    lowering of the kernel for each."""
    traced = program.trace(*args)
    calls = reader_calls(traced.jaxpr.jaxpr, f"{READERS[name]}_kernel")
    assert len(calls) == READS[name]
    assert len({id(j) for j in calls}) == LOWERINGS.get(name, 1)
    lowered = traced.lower()
    assert lowered.as_text().count(
        f'kernel_name = "{READERS[name]}"') == LOWERINGS.get(name, 1)
    return lowered
#: the cells whose chip holds a thin share of each layer's experts
THIN = ("pangu_step", "laguna_step", "gigachat_step")
#: the cells whose share is 8 experts: it keeps its full-length path,
#: XLA's product, for a launch that overflows the short one
EIGHT_HELD = ("pangu_step", "gigachat_step")


def state_traffic(hlo: str, cache: dict) -> tuple:
    """Of the arrays the size of one linear layer's delta-rule states
    (``cache["delta"]``: float32, all the slots'): the operations of an
    optimised module's entry computation that write one and those that
    read one, a layer."""
    states = cache["delta"]
    made, read = logits_sized(hlo, states[0].size)
    return len(made) / len(states), len(read) / len(states)
#: the fusions that read the head's ``f32[N, V]`` (``unit_layers.choose``:
#: the
#: choice; the sum of exponentials where the log-probability is used)
LOGITS_READ = {"sdar_pass": 2, "nemotron_step": 1}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_step_compiled_for_a_v5e_copies_no_slot_buffer_whole(
        one_chip, no_compile_cache, monkeypatch, name):
    """The optimised ``lfm2_step``, ``sdar_pass`` and ``nemotron_step`` at
    the cells' sizes hold no ``copy`` of as many elements as the smallest
    of the buffers a step writes per slot and place (the routes' record);
    both kernels of this repo are in them.  On the CPU the rules answer
    None: the test steers them, not an option of the program."""
    monkeypatch.setattr(gm, "_tiles_here", gm.tile_rule)
    monkeypatch.setattr(sa, "_tiles_here", sa.tile_rule)
    monkeypatch.setattr(sa, "_latent_tiles_here", sa.latent_tile_rule)
    monkeypatch.setattr(unit_backbone, "_layers_once_here",
                        lambda: unit_backbone.LAYERS_ONCE)
    backbone, cache, args = step_shapes(name, one_chip)
    per_place = [int(np.prod(a.shape)) for a in (
        cache["routes"], *cache.get("k", ()), *cache.get("v", ()),
        *cache.get("latent", ()))]
    assert min(per_place) == cache["routes"].size
    compiled = lowered_once_a_geometry(backbone.build_step(), args,
                                       name).compile()
    # a program of the lattice is tens of megabytes of the machine's
    # compile cache, 192 MiB for 15 of them: 10-18 MB each here, and
    # ``laguna_step`` 70 where its eight layers' code is emitted eight times
    assert compiled.memory_analysis().generated_code_size_in_bytes < CODE_MB[
        "step"] * 1e6
    hlo = compiled.as_text()
    assert READERS[name] in hlo and "grouped_matmul" in hlo
    assert whole_buffer_copies(hlo, min(per_place)) == []
    if name in LOGITS_READ:
        # behind the head the logits are read where it left them: no second
        # array of their size (a copy to another tiling, a ``log_softmax``
        # written out), one fusion that chooses and, where the confidence
        # is used, one that sums
        rows = CELLS[name][2] * backbone.block_length
        made, read = logits_sized(hlo, rows * backbone.cfg.vocab_size)
        assert [kind for _, kind in made] == ["fusion"]
        assert len(read) == LOGITS_READ[name]
        # the draw's words are the generator's own operation (``rbg``),
        # read by the choice's fusion alone
        words, read = logits_sized(hlo, rows * backbone.cfg.vocab_size,
                                   "u32|s32")
        assert [kind for _, kind in words] == ["rng-bit-generator"]
        assert len(read) == 1
    # a thin share's program holds both paths: the short one on the kernel
    # and, for a launch that overflows it, the full-length one
    assert ("conditional" in hlo) == (name in THIN)
    if "delta" in cache:
        # a layer's states are read twice (both reductions in one pass,
        # then the update) and written once, where they lie
        assert whole_buffer_copies(hlo, cache["delta"][0].size, "f32") == []
        assert state_traffic(hlo, cache) == (1, 2)


@pytest.mark.parametrize("name", ["lfm2_step", "nemotron_step",
                                  "pangu_step", "laguna_step",
                                  "gigachat_step"])
def test_a_carrying_step_compiled_for_a_v5e_copies_no_slot_buffer_whole(
        one_chip, no_compile_cache, monkeypatch, name):
    """The step that carries an arrival, at the cells' sizes and their
    longest text bucket (192): the prompt's ``write_slot`` follows the
    step's ``write_rows`` on each donated buffer, and none is copied whole
    for it (keys, values, the routes' record; nor a layer's recurrent
    states, float32, half a gigabyte each at 256 slots); the expert
    products over ``(S + T) x k`` rows are the kernel's and the live rows'
    attention too."""
    monkeypatch.setattr(gm, "_tiles_here", gm.tile_rule)
    monkeypatch.setattr(sa, "_tiles_here", sa.tile_rule)
    monkeypatch.setattr(sa, "_latent_tiles_here", sa.latent_tile_rule)
    monkeypatch.setattr(unit_backbone, "_layers_once_here",
                        lambda: unit_backbone.LAYERS_ONCE)
    backbone, cache, args = step_shapes(name, one_chip)
    arrival = (jax.ShapeDtypeStruct((192,), jnp.int32),
               *(jax.ShapeDtypeStruct((), t) for t in (
                   jnp.int32, jnp.int32, F32, jnp.int32)))
    args += tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                  for a in arrival)
    compiled = lowered_once_a_geometry(backbone.build_step_admit(), args,
                                       name).compile()
    # 18-28 MB at the longest text bucket (``laguna_step_admit`` 100 with
    # its layers' code emitted eight times)
    assert compiled.memory_analysis().generated_code_size_in_bytes < CODE_MB[
        "step_admit"] * 1e6
    hlo = compiled.as_text()
    assert f"{name}_admit" in hlo
    assert READERS[name] in hlo and "grouped_matmul" in hlo
    # the operation's name, not the word: the module's stack frames name
    # whatever function first traced a cached helper, a test's among them
    # (a share of 8 experts keeps its full-length path, XLA's product, for
    # a launch that overflows the short one; with 32 held that path's 3584
    # rows are the kernel's too)
    assert ("%ragged-dot" in hlo) == (name in EIGHT_HELD)
    assert ("conditional" in hlo) == (name in THIN)
    assert whole_buffer_copies(hlo, cache["routes"].size) == []
    for state in (*cache.get("ssm", ())[:1], *cache.get("delta", ())[:1]):
        assert whole_buffer_copies(hlo, state.size, "f32") == []
    if "delta" in cache:
        # the prompt's state lands in its slot of the step's result: one
        # more operation names the array, none moves it whole
        assert state_traffic(hlo, cache) == (2, 3)
