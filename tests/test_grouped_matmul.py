"""The expert products' kernel (``sonata_tpu/ops/grouped_matmul.py``), held
to ``jax.lax.ragged_dot`` in interpret mode at tiny widths, its tile rule
as a pure function.  The kernel compiled at the benchmark's shapes for the
chip the cells run on: ``test_compiled_for_v5e.py``."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from sonata_tpu.models import lfm2, unit_layers

gm = importlib.import_module("sonata_tpu.ops.grouped_matmul")
Tiles = gm.Tiles

K, N = 128, 256
#: name -> (rows, sizes, tiles)
CASES = {
    "first_group_empty": (64, [0, 20, 30, 14], Tiles(32, 128)),
    "middle_group_empty": (64, [20, 0, 30, 14], Tiles(32, 128)),
    "last_group_empty": (64, [20, 30, 14, 0], Tiles(32, 128)),
    "every_other_group_empty": (64, [0, 40, 0, 24], Tiles(32, 256)),
    "a_group_spans_two_row_tiles": (64, [10, 40, 14], Tiles(32, 128)),
    "a_group_spans_three_row_tiles": (96, [20, 70, 6], Tiles(32, 128)),
    "every_row_on_one_expert": (64, [0, 0, 64, 0], Tiles(16, 128)),
    "every_row_on_the_last_expert": (64, [0, 0, 0, 64], Tiles(32, 256)),
    "four_rows_an_expert": (64, [4] * 16, Tiles(32, 128)),
    "seventeen_rows_an_expert": (136, [17] * 8, Tiles(32, 128)),
    "seventeen_rows_in_tiles_of_128": (272, [17] * 16, Tiles(128, 256)),
    "trailing_rows_in_the_last_tile": (64, [10, 9, 8, 7], Tiles(32, 128)),
    "trailing_rows_fill_whole_tiles": (128, [5, 0, 6, 3], Tiles(32, 128)),
    "rows_no_multiple_of_the_tile": (50, [12, 0, 30, 8], Tiles(32, 128)),
    "one_row": (16, [0, 1, 0], Tiles(16, 128)),
    "no_row_at_all": (64, [0, 0, 0, 0], Tiles(32, 128)),
}


def operands(rows: int, groups: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, K)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((groups, K, N)), jnp.bfloat16)
    return x, w


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_ragged_dot_on_the_rows_of_the_groups(name):
    """bfloat16 in, float32 out, equal to ``ragged_dot`` to the float32
    rounding of a sum in another order; rows behind the last group are
    nobody's and may hold anything."""
    rows, sizes, tiles = CASES[name]
    x, w = operands(rows, len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    want = lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    got = gm.grouped_matmul_kernel(x, w, sizes, tiles, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    inside = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:inside]),
                               np.asarray(want[:inside]), rtol=2e-6,
                               atol=2e-5)


def test_the_visit_list_reads_each_touched_group_once():
    """One visit a (group, row tile it touches), the groups in order, no
    empty group among them, and the visits past the last repeat it (the
    pipeline fetches nothing for a block index that does not change)."""
    sizes = jnp.asarray([10, 0, 40, 0, 14, 0], jnp.int32)
    offsets, group, tile, count = (np.asarray(a) for a in gm.visit_list(
        sizes, 64, 32))
    assert offsets.tolist() == [0, 10, 10, 50, 50, 64, 64]
    assert int(count[0]) == 4 and len(group) == gm.max_visits(64, 6, 32)
    assert group.tolist() == [0, 2, 2, 4, 4, 4, 4]
    assert tile.tolist() == [0, 0, 1, 1, 1, 1, 1]
    # a group's weights are fetched when the group of a visit changes
    assert int((np.diff(group[:4]) != 0).sum()) + 1 == int((sizes > 0).sum())


def held_layer(cfg, held, seed: int = 5):
    rng = np.random.default_rng(seed)
    h, i = cfg.hidden_size, cfg.moe_intermediate_size

    def draw(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)

    return {"w13": draw(held[1], h, 2 * i), "w2": draw(held[1], i, h),
            "router": draw(h, cfg.num_experts, dtype=jnp.float32),
            "expert_bias": jnp.zeros((cfg.num_experts,), jnp.float32)}


@pytest.mark.parametrize("held", [(0, 8), (0, 3), (2, 4), (5, 3)])
def test_moe_ffn_over_the_kernel_is_moe_ffn_over_ragged_dot(monkeypatch,
                                                            held):
    """``held`` subsets and masked tokens put rows behind the last group;
    ``moe_ffn`` alone knows, and masks what comes back for them."""
    cfg = lfm2.Lfm2Config(
        hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
        rope_theta=1e6, conv_L_cache=3, intermediate_size=256,
        moe_intermediate_size=128, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1.0, num_dense_layers=0, norm_eps=1e-5,
        vocab_size=512, layer_types=("conv",), head_dim=32)
    p = held_layer(cfg, held)
    u = jnp.asarray(np.random.default_rng(6).standard_normal((24, 128)),
                    jnp.float32)
    valid = jnp.arange(24) < 19
    want = unit_layers.moe_ffn(u, p, cfg, held, valid)
    monkeypatch.setattr(unit_layers, "grouped_matmul", functools.partial(
        gm.grouped_matmul_kernel, tiles=Tiles(16, 128), interpret=True))
    got = unit_layers.moe_ffn(u, p, cfg, held, valid)
    assert np.all(np.isfinite(np.asarray(got[0])))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.array_equal(np.asarray(got[2]), np.asarray(want[2]))


def test_off_a_tpu_the_function_is_ragged_dot():
    x, w = operands(64, 4)
    sizes = jnp.asarray([10, 0, 30, 24], jnp.int32)
    graph = str(jax.make_jaxpr(gm.grouped_matmul)(x, w, sizes))
    assert "ragged_dot" in graph and "pallas_call" not in graph
    assert gm.implementation(256, 64, 2048, 3072, jnp.bfloat16) == \
        "ragged_dot"


#: (rows, groups, k, n) of the expert products of the benchmark's programs:
#: the two step programs, and the prefill programs at the text buckets the
#: cells' prompts (68-182 ids) fall in
STEP_SHAPES = {
    "lfm2_step.w13": (256, 64, 2048, 3072),
    "lfm2_step.w2": (256, 64, 1536, 2048),
    "sdar_pass.w13": (2048, 128, 2048, 1536),
    "sdar_pass.w2": (2048, 128, 768, 2048),
    # 256 rows x 6 on the 64 held experts; 1856 columns in 1920 lanes
    "nemotron_step.w13": (1536, 64, 2688, 1920),
    "nemotron_step.w2": (1536, 64, 1920, 2688),
    # 256 rows x 8 of which the short path of a thin share (8 of 256 experts
    # held) takes 256; a weight block is a quarter, a half of a matrix
    "pangu_step.w13": (256, 8, 7680, 4096),
    "pangu_step.w2": (256, 8, 2048, 7680),
}
PREFILL_SHAPES = {
    f"{name}_prefill{t}.{w}": (t * top, groups, k, n)
    for name, top, groups, shapes in (
        ("lfm2", 4, 64, {"w13": (2048, 3072), "w2": (1536, 2048)}),
        ("sdar", 8, 128, {"w13": (2048, 1536), "w2": (768, 2048)}),
        ("nemotron", 6, 64, {"w13": (2688, 1920), "w2": (1920, 2688)}))
    for t in (96, 128, 192) for w, (k, n) in shapes.items()}
#: a carrying step of the thin share: the short path's rows at 256 + t tokens
PREFILL_SHAPES.update({
    f"pangu_step_admit{t}.{w}": (rows, 8, k, n)
    for t, rows in ((128, 256), (192, 384), (256, 384))
    for w, (k, n) in {"w13": (7680, 4096), "w2": (2048, 7680)}.items()})
SHAPES = {**STEP_SHAPES, **PREFILL_SHAPES}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_tile_rule_at_the_benchmarks_shapes(name):
    """Tiles that divide the shape, fit the VMEM budget the module states,
    and keep the masked multiply under the time the weights take to
    stream; the step programs' shapes all get tiles."""
    rows, groups, k, n = SHAPES[name]
    tiles = gm.tile_rule(rows, groups, k, n, jnp.bfloat16)
    if name in STEP_SHAPES:
        assert tiles is not None
    if tiles is None:
        return
    # rows no multiple of the tile (a prefill of 96 ids x 6) are padded by
    # the kernel: a few rows of activations, no weight
    assert n % tiles.tn == 0 and (rows % tiles.tm == 0 or "nemotron" in name)
    assert tiles.tm % 16 == 0 and tiles.tn % 128 == 0
    assert gm.vmem_bytes(tiles, k, 2, 2) <= gm.VMEM_BUDGET
    assert gm.mxu_seconds(rows, groups, k, n, tiles.tm) <= \
        gm.stream_seconds(rows, groups, k, n, 2)


def test_the_tile_rule_reads_the_shape_alone():
    # what the chip's table says (PERF.md §5): a tile of the MXU's 128 rows
    # and a group's whole matrix a block, at 4 and at 17 rows a group
    assert {name: gm.tile_rule(*shape, jnp.bfloat16)
            for name, shape in STEP_SHAPES.items()} == {
        "lfm2_step.w13": Tiles(128, 3072), "lfm2_step.w2": Tiles(128, 2048),
        "sdar_pass.w13": Tiles(128, 1536), "sdar_pass.w2": Tiles(128, 2048),
        "nemotron_step.w13": Tiles(128, 1920),
        "nemotron_step.w2": Tiles(128, 2688),
        # an expert's matrix is 63 and 31 MB: a block is a quarter, a half
        "pangu_step.w13": Tiles(128, 1024),
        "pangu_step.w2": Tiles(128, 3840)}
    # all 2048 assignments of a layer not told of its thin share: the
    # worst-case masked multiply outlasts the 8 experts' stream
    assert gm.tile_rule(2048, 8, 7680, 4096, jnp.bfloat16) is None
    assert gm.tile_rule(2048, 8, 2048, 7680, jnp.bfloat16) is None
    assert gm.tile_rule(64, 8, 7680, 4096, jnp.bfloat16) == Tiles(64, 1024)
    # fewer rows than a tile: all of them, in sublanes of 16
    assert gm.tile_rule(24, 64, 2048, 3072, jnp.bfloat16) == Tiles(32, 3072)
    # many rows a group: no weight stream, XLA's product stays
    assert gm.tile_rule(64 * 1024, 64, 2048, 3072, jnp.bfloat16) is None
    # widths the lanes do not divide: XLA would hand the kernel a copy of
    # every group's matrix (the module says why); the caller pads to lanes
    assert gm.tile_rule(1536, 64, 2688, 1856, jnp.bfloat16) is None
    assert gm.tile_rule(1536, 64, 1856, 2688, jnp.bfloat16) is None
    assert gm.tile_rule(256, 64, 2048, 3000, jnp.bfloat16) is None
    assert gm.tile_rule(256, 64, 100, 3072, jnp.bfloat16) is None
