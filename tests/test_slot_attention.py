"""The slots' keys and values and a step's attention over them
(``sonata_tpu/ops/slot_attention.py``): the kernel in interpret mode (its
copies and its trip counts from ``upto``) and the fallback against the
expressions ``unit_layers.attn_op_step`` and ``sdar.attn_op_block`` had until
PR 37, at the cells' geometries; the places the kernel moves; the two ways a
slot is written; and the tile rule as a pure function.  What the chip's
compiler makes of it is in ``test_compiled_for_v5e.py``."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tools.profile_start import equations

sa = importlib.import_module("sonata_tpu.ops.slot_attention")
Tiles = sa.Tiles
BF16, F32 = jnp.bfloat16, jnp.float32

#: name -> (kv, g, d, b) of the cells' step programs, and in the tests 3
#: slots of 256 places (one trip of two chunks of 128) or of 1024 (two
#: trips of four)
GEOMETRIES = {"lfm2_step": (8, 4, 64, 1), "sdar_pass": (4, 8, 128, 4),
              "nemotron_step": (2, 16, 128, 1),
              # the two kinds of layer of one backbone: 48 and 64 query
              # heads over 8 of 128
              "laguna_full": (8, 6, 128, 1), "laguna_ring": (8, 8, 128, 1)}
S, P, TP = 3, 256, 128
#: the kernel against the einsum is held to 6e-3 absolute and no more in
#: every geometry but this one, whose ``one_place`` case reads 6.96e-3 on 6
#: of its 24576 numbers (at values of 1.7: 2^-8 of themselves, what a
#: bfloat16 probability is from its float32), so it alone gets that term
KERNEL_RTOL = {"laguna_ring": 2 ** -8}
#: name -> a slot's length in each of the 3 slots: one place, edges (a
#: chunk's at 128) from both sides, every place, and lengths inside chunks
UPTOS = {"one_place": [1, 1, 4], "a_tiles_edge": [64, 128, 192],
         "past_a_tiles_edge": [65, 129, 193], "every_place": [256, 256, 255],
         "inside_tiles": [37, 100, 211]}
#: name -> (places a slot, a slot's length in each of the 3 slots): those
#: cases, and over 1024 places a chunk's edge, one place past it (at 512 a
#: trip's), nothing beside a full slot (the copies run on over empty slots)
WALK_UPTOS = {
    **{name: (P, upto) for name, upto in UPTOS.items()},
    "a_chunks_edge": (1024, [128, 512, 640]),
    "past_a_chunks_edge": (1024, [129, 513, 641]),
    "before_a_chunks_edge": (1024, [127, 511, 639]),
    "nothing_beside_a_full_slot": (1024, [0, 1024, 0]),
    "full_slots_beside_nothing": (1024, [1024, 0, 1023]),
    "nothing_one_place_and_the_last": (1024, [0, 1, 1024]),
}


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


def operands(name: str, seed: int = 0, positions: int = P):
    kv, g, d, b = GEOMETRIES[name]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((S, b, kv, g, d)), F32)
    k = jnp.asarray(rng.standard_normal((S, positions, kv, d)), BF16)
    v = jnp.asarray(rng.standard_normal((S, positions, kv, d)), BF16)
    return q, k, v


def stored(a):
    return a.reshape(sa.stored_shape(*a.shape))


@pytest.mark.parametrize("upto", sorted(WALK_UPTOS))
@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_the_kernel_and_the_fallback_are_the_einsum_they_replace(name, upto):
    """bfloat16 into the products, float32 out: the fallback to the
    rounding of the same sums, the kernel (interpreted: its copies, its
    trip counts from ``upto``) to that of probabilities rounded to bfloat16
    before they are normalised and not after."""
    positions, upto = WALK_UPTOS[upto]
    q, k, v = operands(name, positions=positions)
    upto = jnp.asarray(upto, jnp.int32)
    want = until_pr37(q, k, v, upto)
    fallback = sa.slot_attention_einsum(q, stored(k), stored(v), upto)
    kernel = sa.slot_attention_kernel(q, stored(k), stored(v), upto,
                                      Tiles(TP), interpret=True)
    assert kernel.shape == want.shape and kernel.dtype == F32
    # a slot that sees nothing: zeros from both, a NaN from the expression
    # of old
    held = np.asarray(upto) > 0
    np.testing.assert_allclose(np.asarray(fallback)[held],
                               np.asarray(want)[held], rtol=1e-6, atol=1e-6)
    assert not np.asarray(fallback)[~held].any()
    assert not np.asarray(kernel)[~held].any()
    np.testing.assert_allclose(np.asarray(kernel)[held],
                               np.asarray(want)[held],
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
    """A ring of 128 places written a position at a time (``write_rows``
    with ``ring``) for rows of 10, 128 and 250 positions, read
    ``ring_upto`` places: what the einsum over a whole buffer gives when it
    sees the last 128 positions alone; a prompt longer than the ring
    (``write_slot`` with ``ring_n``) leaves the same places; and a whole
    buffer beside it is read as ever."""
    kv, g, d, b = GEOMETRIES[name]
    window, lengths = 128, [10, 128, 250]
    q, k, v = operands(name, seed=4)
    ring = [jnp.zeros(sa.stored_shape(S, window, kv, d), BF16)] * 2
    for at in range(max(lengths)):
        # a slot past its row's end keeps its last place: written again
        pos = jnp.minimum(at, jnp.asarray(lengths) - 1)[:, None]
        rows = jnp.arange(S)[:, None]
        ring = [sa.write_rows(buf, a[rows, pos], pos, ring=True)
                for buf, a in zip(ring, (k, v))]
    upto = jnp.asarray(lengths, jnp.int32)
    assert sa.ring_upto(upto, window).tolist() == [10, 128, 128]
    read = (sa.slot_attention_einsum if reader == "fallback" else
            functools.partial(sa.slot_attention_kernel, tiles=Tiles(TP),
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
    # a prompt of 250 (padded to 256) into a ring of 128: the same places
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


@pytest.mark.parametrize("reader", ["kernel", "fallback"])
def test_a_ring_of_512_places_read_after_it_wrapped_is_the_window(reader):
    """The cell's ring (one trip of four chunks) under prompts of 300, 512
    and 900 ids padded to 1024 (``write_slot`` with ``ring_n``): read
    ``ring_upto`` places, a row sees its last 512 positions and no other,
    in whatever order they lie."""
    kv, g, d, b = GEOMETRIES["laguna_ring"]
    window, lengths = 512, [300, 512, 900]
    q, k, v = operands("laguna_ring", seed=5, positions=1024)
    ring = [jnp.zeros(sa.stored_shape(S, window, kv, d), BF16)] * 2
    for slot, n in enumerate(lengths):
        ring = [sa.write_slot(buf, a[slot], slot, ring_n=jnp.int32(n))
                for buf, a in zip(ring, (k, v))]
    upto = jnp.asarray(lengths, jnp.int32)
    assert sa.ring_upto(upto, window).tolist() == [300, 512, 512]
    read = (sa.slot_attention_einsum if reader == "fallback" else
            functools.partial(sa.slot_attention_kernel, tiles=Tiles(TP),
                              interpret=True))
    got = np.asarray(read(q, *ring, sa.ring_upto(upto, window)))
    at = jnp.arange(1024)[None, :]
    seen = (at < upto[:, None]) & (at >= upto[:, None] - window)
    scores = jnp.einsum("sbkgd,spkd->skgbp", q.astype(BF16), k,
                        preferred_element_type=F32) / jnp.sqrt(F32(d))
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, None, None, :], scores, -jnp.inf), -1)
    want = np.asarray(jnp.einsum("skgbp,spkd->sbkgd", probs.astype(BF16), v,
                                 preferred_element_type=F32))
    np.testing.assert_allclose(got, want, rtol=0, atol=6e-3)


def test_places_moved_are_whole_chunks_up_to_a_slots_length():
    assert [sa.places_moved(n, 128) for n in (0, 1, 128, 129, 1024)] == [
        0, 128, 128, 256, 1024]
    # every place of the buffer where the einsum reads: off a TPU the
    # chunk is the buffer's span, a ring's its window
    assert sa.reach(1024, 8, 6, 128, 1) == 1024
    assert sa.reach(512, 8, 8, 128, 1) == 512
    assert sa.places_moved(347, sa.reach(512, 8, 8, 128, 1)) == 512
    # one function under the latent reader's name too
    assert sa.latent_places is sa.places_moved


@pytest.mark.parametrize("chunk", [128, 256])
@pytest.mark.parametrize("name", ["lfm2_step", "laguna_ring"])
def test_the_kernel_moves_its_counted_places_and_no_more(name, chunk):
    """The counter's function against the kernel's copies and trip counts
    (heads that share a lane group; eight lane groups): a NaN in any place
    of keys and values from ``places_moved`` on never reaches a result (a
    place that was fetched would, through ``0 x NaN`` in the second
    product), and one in the last place before it does, masked or not."""
    q, k, v = operands(name, seed=6, positions=1024)
    k, v = stored(k), stored(v)
    read = functools.partial(sa.slot_attention_kernel, tiles=Tiles(chunk),
                             interpret=True)
    for upto in ([1, 513, 0], [128, 700, 1024], [257, 0, 512]):
        moved = sa.places_moved(np.asarray(upto), chunk)
        base = np.asarray(read(q, k, v, jnp.asarray(upto, jnp.int32)))
        assert np.all(np.isfinite(base))
        beyond, inside = [k, v], [k, v]
        for slot, places in enumerate(moved):
            beyond = [a.at[slot, int(places):].set(jnp.nan) for a in beyond]
            if places:
                inside = [a.at[slot, int(places) - 1].set(jnp.nan)
                          for a in inside]
        assert np.array_equal(
            np.asarray(read(q, *beyond, jnp.asarray(upto, jnp.int32))), base)
        reached = np.asarray(read(q, *inside, jnp.asarray(upto, jnp.int32)))
        for slot, places in enumerate(moved):
            assert np.all(np.isnan(reached[slot])) == bool(places)


#: name -> (slots, places) of a cell and the equations its kernel's jaxpr
#: may hold, the queries' and results' re-laying with it: what a start
#: traces once a process and lowers once a program (the grid over tiles
#: held 180-296 and was traced and lowered once a layer; the walk with its
#: lane groups unrolled in each of four bodies 962 at ``lfm2_step`` and
#: 1470 at ``laguna_full``: PERF.md section 6, PR 45)
EQUATIONS = {"lfm2_step": ((64, 1024), 420), "sdar_pass": ((64, 1024), 420),
             "nemotron_step": ((256, 1024), 380),
             "laguna_full": ((256, 1024), 480),
             "laguna_ring": ((256, 512), 480)}


@pytest.mark.parametrize("name", sorted(EQUATIONS))
def test_the_kernels_jaxpr_stays_small_at_the_cells_geometries(name):
    """No clock: the start's cost is the equations a trace makes and a
    lowering walks, whatever the lane groups (4 at ``lfm2_step``, 8 at
    ``laguna``'s two) and the chunks a trip."""
    kv, g, d, b = GEOMETRIES[name]
    (slots, places), most = EQUATIONS[name]
    buf = jax.ShapeDtypeStruct(sa.stored_shape(slots, places, kv, d), BF16)
    jaxpr = jax.make_jaxpr(functools.partial(
        sa.slot_attention_kernel, tiles=sa.tile_rule(places, kv, g, d, b)))(
        jax.ShapeDtypeStruct((slots, b, kv, g, d), F32), buf, buf,
        jax.ShapeDtypeStruct((slots,), jnp.int32))
    assert "pallas_call" in str(jaxpr)
    assert equations(jaxpr.jaxpr) <= most


def test_layers_of_one_shape_share_one_trace_of_the_kernel():
    """The kernel is a jitted function of its own: two layers of one
    geometry in one program are two calls of one jaxpr (one trace a
    process, one lowering a program), a layer of another geometry its
    own."""
    kv, g, d, b = GEOMETRIES["laguna_ring"]
    q, k, v = operands("laguna_ring")
    tiles = Tiles(TP)

    def layers(q, k, v, upto):
        for _ in range(2):
            q = q + sa.slot_attention_kernel(q, stored(k), stored(v), upto,
                                             tiles)
        return sa.slot_attention_kernel(q[:, :, :, :6], stored(k), stored(v),
                                        upto, tiles)

    jaxpr = jax.make_jaxpr(layers)(q, k, v, jnp.asarray([5, 6, 7]))
    calls = [eqn for eqn in jaxpr.jaxpr.eqns
             if eqn.params.get("name") == "slot_attention_kernel"]
    assert len(calls) == 3
    assert calls[0].params["jaxpr"] is calls[1].params["jaxpr"]
    assert calls[2].params["jaxpr"] is not calls[0].params["jaxpr"]
    assert str(jaxpr).count("pallas_call") == 2


#: (positions, kv, g, d, b) -> the chunk, or None where the einsum stays
RULE = {
    # a lane group of scores a chunk, whatever the row's lanes: 512 ...
    "lfm2_step": ((1024, 8, 4, 64, 1), Tiles(128)),
    "sdar_pass": ((1024, 4, 8, 128, 4), Tiles(128)),
    # ... 256 ...
    "nemotron_step": ((1024, 2, 16, 128, 1), Tiles(128)),
    # ... 1024: a whole cache in two trips of four chunks, a ring of 512
    # in one
    "laguna_full": ((1024, 8, 6, 128, 1), Tiles(128)),
    "laguna_ring": ((512, 8, 8, 128, 1), Tiles(128)),
    "1536_positions": ((1536, 8, 4, 64, 1), Tiles(128)),
    "positions_three_chunks_hold": ((384, 8, 4, 64, 1), Tiles(128)),
    "heads_of_256": ((1024, 2, 4, 256, 1), Tiles(128)),
    "heads_of_32_four_a_lane_group": ((1024, 8, 4, 32, 1), Tiles(128)),
    "fewer_positions_than_a_lane_group": ((64, 8, 4, 64, 1), None),
    "heads_the_lanes_do_not_divide": ((1024, 8, 4, 96, 1), None),
    "fewer_heads_than_a_lane_group": ((1024, 1, 4, 64, 1), None),
    "the_tiny_voices": ((256, 2, 2, 16, 1), None),
    "positions_the_chunk_does_not_divide": ((1000, 8, 4, 64, 1), None),
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
#: the per-head reader's cases: both kernels walk a slot in the same way
LATENT_UPTOS = WALK_UPTOS


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
