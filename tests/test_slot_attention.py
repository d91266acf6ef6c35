"""The slots' keys and values and a step's attention over them
(``sonata_tpu/ops/slot_attention.py``): the kernel in interpret mode and
the fallback against the expressions ``lfm2.attn_op_step`` and
``sdar.attn_op_block`` had until PR 37, at the three cells' geometries; the
two ways a slot is written; and the tile rule as a pure function.  What the
chip's compiler makes of it is in ``test_compiled_for_v5e.py``."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sa = importlib.import_module("sonata_tpu.ops.slot_attention")
Tiles = sa.Tiles
BF16, F32 = jnp.bfloat16, jnp.float32

#: name -> (kv, g, d, b) of the three cells' step programs, and in the
#: tests 3 slots of 256 places in tiles of 64
GEOMETRIES = {"lfm2_step": (8, 4, 64, 1), "sdar_pass": (4, 8, 128, 4),
              "nemotron_step": (2, 16, 128, 1),
              # the two kinds of layer of one backbone: 48 and 64 query
              # heads over 8 of 128
              "laguna_full": (8, 6, 128, 1), "laguna_ring": (8, 8, 128, 1)}
S, P, TP = 3, 256, 64
#: the kernel against the einsum is held to 6e-3 absolute and no more in
#: every geometry but this one, whose ``one_place`` case reads 6.96e-3 on 6
#: of its 24576 numbers (at values of 1.7: 2^-8 of themselves, what a
#: bfloat16 probability is from its float32), so it alone gets that term
KERNEL_RTOL = {"laguna_ring": 2 ** -8}
#: name -> a slot's length in each of the 3 slots: one place, a tile's
#: edge from both sides, every place, and lengths inside tiles
UPTOS = {"one_place": [1, 1, 4], "a_tiles_edge": [64, 128, 192],
         "past_a_tiles_edge": [65, 129, 193], "every_place": [256, 256, 255],
         "inside_tiles": [37, 100, 211]}


def until_pr37(q, k_buf, v_buf, upto):
    """``attn_op_step`` (``b`` 1) and ``attn_op_block`` as they were, over
    buffers ``[S, P, kv, d]``."""
    d, span = q.shape[-1], k_buf.shape[1]
    scores = jnp.einsum("sbkgd,spkd->skgbp", q.astype(BF16), k_buf,
                        preferred_element_type=F32) / jnp.sqrt(F32(d))
    seen = jnp.arange(span)[None, :] < upto[:, None]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, None, None, :], scores, -jnp.inf), -1)
    return jnp.einsum("skgbp,spkd->sbkgd", probs.astype(BF16), v_buf,
                      preferred_element_type=F32)


def operands(name: str, seed: int = 0):
    kv, g, d, b = GEOMETRIES[name]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((S, b, kv, g, d)), F32)
    k = jnp.asarray(rng.standard_normal((S, P, kv, d)), BF16)
    v = jnp.asarray(rng.standard_normal((S, P, kv, d)), BF16)
    return q, k, v


def stored(a):
    return a.reshape(sa.stored_shape(*a.shape))


@pytest.mark.parametrize("upto", sorted(UPTOS))
@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_the_kernel_and_the_fallback_are_the_einsum_they_replace(name, upto):
    """bfloat16 into the products, float32 out: the fallback to the
    rounding of the same sums, the kernel to that of probabilities rounded
    to bfloat16 before they are normalised and not after."""
    q, k, v = operands(name)
    upto = jnp.asarray(UPTOS[upto], jnp.int32)
    want = until_pr37(q, k, v, upto)
    fallback = sa.slot_attention_einsum(q, stored(k), stored(v), upto)
    kernel = sa.slot_attention_kernel(q, stored(k), stored(v), upto,
                                      Tiles(TP), interpret=True)
    assert kernel.shape == want.shape and kernel.dtype == F32
    np.testing.assert_allclose(np.asarray(fallback), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(want),
                               rtol=KERNEL_RTOL.get(name, 0), atol=6e-3)


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_a_slot_that_sees_nothing_gives_zeros(name):
    q, k, v = operands(name)
    upto = jnp.asarray([0, 5, 0], jnp.int32)
    for got in (sa.slot_attention_einsum(q, stored(k), stored(v), upto),
                sa.slot_attention_kernel(q, stored(k), stored(v), upto,
                                         Tiles(TP), interpret=True)):
        got = np.asarray(got)
        assert np.all(got[[0, 2]] == 0.0) and np.all(np.isfinite(got))
        assert np.abs(got[1]).max() > 0.0


@pytest.mark.parametrize("reader", ["kernel", "fallback"])
def test_a_block_is_whole_inside_and_blind_past_it(reader):
    """``b = 4``: every query of a slot's block sees the block's last
    place, and none sees a place at or past ``upto``."""
    q, k, v = operands("sdar_pass")
    upto = jnp.asarray([8, 100, 132], jnp.int32)
    read = (sa.slot_attention_einsum if reader == "fallback" else
            functools.partial(sa.slot_attention_kernel, tiles=Tiles(TP),
                              interpret=True))
    base = np.asarray(read(q, stored(k), stored(v), upto))
    rows = np.arange(S)
    last, past = np.asarray(upto) - 1, np.asarray(upto)
    inside = np.asarray(read(q, stored(k), stored(
        v.at[rows, last].set(v[rows, last] + 4.0)), upto))
    # every one of the block's four queries, every head
    assert np.all(np.abs(inside - base).max(-1) > 1e-3)
    outside = np.asarray(read(q, stored(k.at[rows, past].set(50.0)), stored(
        v.at[rows, past].set(50.0)), upto))
    assert np.array_equal(outside, base)


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_written_by_rows_or_by_slot_a_slot_reads_the_same(name):
    """A prefill writes a slot's row whole, a step a place a slot (a pass
    a block): the same keys and values either way."""
    kv, g, d, b = GEOMETRIES[name]
    q, k, v = operands(name, seed=1)
    t = 32
    empty = jnp.zeros(sa.stored_shape(S, P, kv, d), BF16)
    by_slot, by_rows = [empty, empty], [empty, empty]
    for i, a in enumerate((k, v)):
        for slot in range(S):
            by_slot[i] = sa.write_slot(by_slot[i], a[slot, :t], slot)
        for start in range(0, t, b):
            pos = jnp.broadcast_to(start + jnp.arange(b), (S, b))
            by_rows[i] = sa.write_rows(by_rows[i], a[:, start:start + b],
                                       pos)
        assert np.array_equal(np.asarray(by_slot[i], F32),
                              np.asarray(by_rows[i], F32))
        assert np.array_equal(
            np.asarray(sa.read_slot(by_slot[i][1], kv, d)[:t], F32),
            np.asarray(a[1, :t], F32))
    upto = jnp.asarray([t, t - b, b], jnp.int32)
    assert np.array_equal(
        np.asarray(sa.slot_attention(q, *by_slot, upto)),
        np.asarray(sa.slot_attention(q, *by_rows, upto)))


@pytest.mark.parametrize("reader", ["kernel", "fallback"])
@pytest.mark.parametrize("name", ["laguna_full", "laguna_ring"])
def test_a_ring_read_as_far_as_it_is_written_is_the_window(name, reader):
    """A ring of 64 places written a position at a time (``write_rows``
    with ``ring``) for rows of 10, 64 and 200 positions, read
    ``ring_upto`` places: what the einsum over a whole buffer gives when it
    sees the last 64 positions alone; a prompt longer than the ring
    (``write_slot`` with ``ring_n``) leaves the same places; and a whole
    buffer beside it is read as ever."""
    kv, g, d, b = GEOMETRIES[name]
    window, lengths = 64, [10, 64, 200]
    q, k, v = operands(name, seed=4)
    ring = [jnp.zeros(sa.stored_shape(S, window, kv, d), BF16)] * 2
    for at in range(max(lengths)):
        # a slot past its row's end keeps its last place: written again
        pos = jnp.minimum(at, jnp.asarray(lengths) - 1)[:, None]
        rows = jnp.arange(S)[:, None]
        ring = [sa.write_rows(buf, a[rows, pos], pos, ring=True)
                for buf, a in zip(ring, (k, v))]
    upto = jnp.asarray(lengths, jnp.int32)
    assert sa.ring_upto(upto, window).tolist() == [10, 64, 64]
    read = (sa.slot_attention_einsum if reader == "fallback" else
            functools.partial(sa.slot_attention_kernel, tiles=Tiles(32),
                              interpret=True))
    got = np.asarray(read(q, *ring, sa.ring_upto(upto, window)))
    # the whole buffer, everything before the window out of sight
    seen = (jnp.arange(P)[None, :] < upto[:, None]) & (
        jnp.arange(P)[None, :] >= upto[:, None] - window)
    scores = jnp.einsum("sbkgd,spkd->skgbp", q.astype(BF16), k,
                        preferred_element_type=F32) / jnp.sqrt(F32(d))
    scores = jnp.where(seen[:, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    want = np.asarray(jnp.einsum("skgbp,spkd->sbkgd", probs.astype(BF16), v,
                                 preferred_element_type=F32))
    np.testing.assert_allclose(got, want, rtol=0, atol=6e-3)
    # a prompt of 200 (padded to 256) into a ring of 64: the same places
    for slot, n in enumerate(lengths):
        by_slot = sa.write_slot(jnp.zeros_like(ring[0]), k[slot], slot,
                                ring_n=jnp.int32(n))
        held = min(n, window)
        latest = [max(p for p in range(n) if p % window == place)
                  for place in range(held)]
        assert np.array_equal(
            np.asarray(sa.read_slot(by_slot[slot], kv, d)[:held], F32),
            np.asarray(k[slot, jnp.asarray(latest)], F32))
        assert np.array_equal(np.asarray(by_slot[slot, :held], F32),
                              np.asarray(ring[0][slot, :held], F32))


def test_a_narrow_record_lies_in_whole_lanes():
    """The routes' record: 8 layers of 4 experts a token are 32 bytes a
    place, stored as a row of 128; what was written comes back."""
    assert sa.stored_shape(64, 1024, 8, 4) == (64, 1024, 128)
    assert sa.stored_shape(64, 1024, 8, 64) == (64, 1024, 512)
    assert sa.stored_shape(256, 1024, 2, 128) == (256, 1024, 256)
    rng = np.random.default_rng(2)
    chose = jnp.asarray(rng.integers(0, 64, (S, 1, 8, 4)), jnp.int32)
    buf = jnp.zeros(sa.stored_shape(S, P, 8, 4), jnp.int8)
    pos = jnp.asarray([[3], [200], [0]], jnp.int32)
    buf = sa.write_rows(buf, chose, pos)
    assert buf.shape == (S, P, 128) and buf.dtype == jnp.int8
    for slot, at in enumerate(np.asarray(pos)[:, 0]):
        got = sa.read_slot(np.asarray(buf[slot]), 8, 4)
        assert got.shape == (P, 8, 4) and got.dtype == np.int8
        assert np.array_equal(got[at], np.asarray(chose[slot, 0]))
        assert not got[np.arange(P) != at].any()


#: (positions, kv, g, d, b) -> tiles, or None where the einsum stays
RULE = {
    # half a megabyte of keys a tile: 512 places of 512 lanes ...
    "lfm2_step": ((1024, 8, 4, 64, 1), Tiles(512)),
    "sdar_pass": ((1024, 4, 8, 128, 4), Tiles(512)),
    # ... 1024 of 256: every place, one tile a slot
    "nemotron_step": ((1024, 2, 16, 128, 1), Tiles(1024)),
    # 256 of 1024 lanes: a whole cache in four tiles, a ring of 512 in two
    "laguna_full": ((1024, 8, 6, 128, 1), Tiles(256)),
    "laguna_ring": ((512, 8, 8, 128, 1), Tiles(256)),
    "1536_positions": ((1536, 8, 4, 64, 1), Tiles(512)),
    "fewer_positions_than_a_tile": ((384, 8, 4, 64, 1), Tiles(384)),
    "heads_of_256": ((1024, 2, 4, 256, 1), Tiles(512)),
    "heads_of_32_four_a_lane_group": ((1024, 8, 4, 32, 1), Tiles(1024)),
    "heads_the_lanes_do_not_divide": ((1024, 8, 4, 96, 1), None),
    "fewer_heads_than_a_lane_group": ((1024, 1, 4, 64, 1), None),
    "the_tiny_voices": ((256, 2, 2, 16, 1), None),
    "positions_the_tile_does_not_divide": ((1000, 8, 4, 64, 1), None),
    "many_query_rows": ((1024, 8, 4, 64, 64), None),
}


@pytest.mark.parametrize("name", sorted(RULE))
def test_the_tile_rule_reads_the_shape_alone(name):
    shape, tiles = RULE[name]
    assert sa.tile_rule(*shape) == tiles


# -- a latent row: keys and values at once ---------------------------------

#: name -> (query heads, a row's values, of which the first are the values)
LATENT = {"pangu_step": (128, 576, 512), "the_tiny_voices": (4, 40, 32),
          "one_lane_group_of_values": (16, 160, 128)}
#: the latent reader's chunk in the tests (the rule's at the cell's shape):
#: 256 places are one trip of two chunks, 1024 two trips of four
CHUNK = 128
#: name -> (places a slot, a slot's length in each of the 3 slots): the
#: per-head reader's cases, and a chunk's edge, one place past it (at 512
#: a trip's), nothing beside a full slot
LATENT_UPTOS = {
    **{name: (P, upto) for name, upto in UPTOS.items()},
    "a_chunks_edge": (1024, [128, 512, 640]),
    "past_a_chunks_edge": (1024, [129, 513, 641]),
    "nothing_beside_a_full_slot": (1024, [0, 1024, 0]),
    "full_slots_beside_nothing": (1024, [1024, 0, 1023]),
}


def latent_operands(name: str, seed: int = 0, positions: int = P):
    g, width, _ = LATENT[name]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((S, 1, g, width)), F32)
    rows = jnp.asarray(rng.standard_normal((S, positions, 1, width)), F32)
    buf = sa.write_slot(
        jnp.zeros(sa.stored_shape(S, positions, 1, width), F32), rows[0], 0)
    for slot in range(1, S):
        buf = sa.write_slot(buf, rows[slot], slot)
    return q, rows[:, :, 0], buf


@pytest.mark.parametrize("upto", sorted(LATENT_UPTOS))
@pytest.mark.parametrize("name", ["pangu_step", "one_lane_group_of_values"])
def test_the_latent_kernel_is_the_einsum_over_one_row_a_place(name, upto):
    """One buffer, read once: the scores over a row's whole width, the
    values its first lanes; the kernel (interpreted: its copies, its trip
    counts from ``upto``) against the einsum over the same buffer and
    against a softmax written out."""
    g, width, values = LATENT[name]
    positions, upto = LATENT_UPTOS[upto]
    q, rows, buf = latent_operands(name, positions=positions)
    assert buf.shape == (S, positions, -(-width // 128) * 128)
    upto = jnp.asarray(upto, jnp.int32)
    scale = 0.07
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("sgw,spw->sgp", q[:, 0], rows) * scale
        seen = jnp.arange(positions)[None, None, :] < upto[:, None, None]
        want = jnp.einsum("sgp,spv->sgv", jax.nn.softmax(
            jnp.where(seen, scores, -jnp.inf), -1), rows[..., :values])
        want = jnp.where((upto > 0)[:, None, None], want, 0.0)
        fallback = sa.latent_attention_einsum(q, buf, upto, values, scale)
        kernel = sa.latent_attention_kernel(q, buf, upto, values, scale,
                                            Tiles(CHUNK), interpret=True)
    assert fallback.shape == kernel.shape == (S, 1, g, values)
    assert kernel.dtype == buf.dtype
    np.testing.assert_allclose(np.asarray(fallback[:, 0]), np.asarray(want),
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(fallback),
                               rtol=0, atol=2e-5)


def test_a_latent_slot_that_sees_nothing_gives_zeros_and_no_later_place():
    g, width, values = LATENT["one_lane_group_of_values"]
    q, rows, buf = latent_operands("one_lane_group_of_values", seed=3)
    upto = jnp.asarray([0, 70, 256], jnp.int32)
    for read in (sa.latent_attention_einsum, functools.partial(
            sa.latent_attention_kernel, tiles=Tiles(CHUNK), interpret=True)):
        base = np.asarray(read(q, buf, upto, values, 0.1))
        assert not base[0].any() and base[1].any()
        later = buf.at[1, 70:].set(50.0)
        assert np.array_equal(np.asarray(read(q, later, upto, values, 0.1)),
                              base)


def test_latent_places_are_whole_chunks_up_to_a_slots_length():
    assert [sa.latent_places(n, 128) for n in (0, 1, 128, 129, 1024)] == [
        0, 128, 128, 256, 1024]
    assert np.array_equal(
        sa.latent_places(np.asarray([0, 347, 512, 819]), 256),
        [0, 512, 512, 1024])
    # every position where the einsum reads: the chunk is the buffer's span
    assert sa.latent_places(347, 1024) == 1024


@pytest.mark.parametrize("chunk", [128, 256])
def test_the_latent_kernel_moves_latent_places_and_no_more(chunk):
    """The counter's function against the kernel's copies and trip counts:
    a NaN in any place from ``latent_places`` on never reaches a result (a
    place that was fetched would, through ``0 x NaN`` in the second
    product), and one in the last place before it does, masked or not."""
    g, width, values = LATENT["one_lane_group_of_values"]
    q, _, buf = latent_operands("one_lane_group_of_values", seed=4,
                                positions=1024)
    read = functools.partial(sa.latent_attention_kernel, values=values,
                             scale=0.1, tiles=Tiles(chunk), interpret=True)
    for upto in ([1, 513, 0], [128, 700, 1024], [257, 0, 512]):
        moved = sa.latent_places(np.asarray(upto), chunk)
        base = np.asarray(read(q, buf, jnp.asarray(upto, jnp.int32)))
        assert np.all(np.isfinite(base))
        beyond, inside = buf, buf
        for slot, places in enumerate(moved):
            beyond = beyond.at[slot, int(places):].set(jnp.nan)
            if places:
                inside = inside.at[slot, int(places) - 1].set(jnp.nan)
        assert np.array_equal(
            np.asarray(read(q, beyond, jnp.asarray(upto, jnp.int32))), base)
        reached = np.asarray(read(q, inside, jnp.asarray(upto, jnp.int32)))
        for slot, places in enumerate(moved):
            assert np.all(np.isnan(reached[slot])) == bool(places)


#: (positions, g, width, values, b) -> the chunk, or None where the einsum
#: stays
LATENT_RULE = {
    # a lane group of scores a chunk: 128 places of 640 lanes
    "pangu_step": ((1024, 128, 576, 512, 1), Tiles(128)),
    "twice_the_positions": ((2048, 128, 576, 512, 1), Tiles(128)),
    "a_chunk_of_positions": ((128, 128, 576, 512, 1), Tiles(128)),
    "a_narrower_row": ((1024, 128, 200, 128, 1), Tiles(256)),
    "fewer_positions_than_a_lane_group": ((64, 128, 576, 512, 1), None),
    "the_tiny_voices": ((256, 4, 40, 32, 1), None),
    "values_wider_than_the_row": ((1024, 128, 576, 640, 1), None),
    "many_query_rows": ((1024, 128, 576, 512, 4), None),
    "positions_the_chunk_does_not_divide": ((1000, 128, 576, 512, 1), None),
}


@pytest.mark.parametrize("name", sorted(LATENT_RULE))
def test_the_latent_tile_rule_reads_the_shape_alone(name):
    shape, tiles = LATENT_RULE[name]
    assert sa.latent_tile_rule(*shape) == tiles
    # the per-head rule has no tiles for such a row (576 is not whole lanes)
    assert sa.tile_rule(1024, 1, 128, 576, 1) is None
    assert sa.stored_shape(256, 1024, 1, 576) == (256, 1024, 640)


def test_off_a_tpu_the_latent_reader_is_the_einsum():
    q, _, buf = latent_operands("the_tiny_voices")
    upto = jnp.asarray([5, 6, 7], jnp.int32)
    read = functools.partial(sa.latent_attention, values=32, scale=0.1)
    graph = str(jax.make_jaxpr(read)(q, buf, upto))
    assert "dot_general" in graph and "pallas_call" not in graph
    assert sa.latent_implementation(1024, 128, 576, 512, 1) == "einsum"
    # which moves every position of a slot, in the rows' type
    assert sa.latent_reach(1024, 128, 576, 512, 1) == 1024
    half = read(q, buf.astype(BF16), upto)
    assert half.dtype == BF16 and read(q, buf, upto).dtype == F32
    assert np.array_equal(
        np.asarray(half, F32), np.asarray(sa.latent_attention_einsum(
            q, buf.astype(BF16), upto, 32, 0.1).astype(BF16), F32))


def test_off_a_tpu_the_function_is_the_einsum():
    q, k, v = operands("lfm2_step")
    upto = jnp.asarray([5, 6, 7], jnp.int32)
    graph = str(jax.make_jaxpr(sa.slot_attention)(q, stored(k), stored(v),
                                                  upto))
    assert "dot_general" in graph and "pallas_call" not in graph
    assert sa.implementation(1024, 8, 4, 64, 1) == "einsum"
