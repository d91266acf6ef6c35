"""The slots' keys and values, and a step's attention over them.

A unit voice's step programs keep, per attention layer, the keys and the
values of every slot: ``slots`` rows of ``positions`` places, each ``kv``
heads of ``d``.  A step writes one place a slot (a pass over a block,
``b``), and then every slot's ``b`` queries read the slot's places
``< upto[slot]``.  This module owns both sides: where the buffers lie
(:func:`stored_shape`), how they are written (:func:`write_rows`,
:func:`write_slot`) and how they are read (:func:`slot_attention`).

**The layout** is ``[slots, positions, kv * d]``: a place is one row of
whole lanes.  A step's write is then a scatter of ``slots * b`` rows into a
buffer that stays where it is, a prefill's is one ``dynamic_update_slice``,
and the reader copies ``(places, kv * d)`` runs of a slot's rows and cuts
the heads out of them in VMEM.  Stored ``[slots, positions, kv, d]`` and
read by an einsum, XLA re-laid every buffer twice a step (it wants the
positions on the lanes for its products and the heads there for its
scatter: PERF.md §5), which cost more than the attention itself.

**The reader** on a TPU is a kernel of this module where :func:`tile_rule`
has a chunk for the shape: one grid step a slot, ``upto`` prefetched as
scalars, the buffers left in HBM.  Inside a grid step the kernel walks the
slot's places as far as ``upto`` says (:func:`places_moved`: whole chunks of
128 places), up to four chunks a trip: a copy of keys and one of values a
chunk, one pass of the online softmax a trip for each lane group of heads,
the copies running ahead of the products from one slot into the next.  No
place past a row's last chunk is fetched or multiplied, and no grid step is
paid for one (PERF.md §5 has it beside the grid over tiles it took the place
of).  Heads narrower than the 128 lanes share a lane group:
the queries of head ``h`` lie in their own rows with zeros in the other
heads' lanes, so one product over the group's lanes gives every head's
scores, and of the product with the values each row keeps its own head's
lanes.  The kernel is a jitted function of its own and its jaxpr is kept
small (the lane groups in one batched product, a trip's copies in a loop,
a body of static size only for a pass's places): tracing and lowering are cached by
nothing, so a start pays them again for every program, and a kernel laid
out flat once a layer made a warm start of ``lfm2-24b-a2b`` 8 s longer
(PERF.md §6, PR 45).  Off a TPU, and where the rule says no, the reader is
the einsum it replaces, over the same buffers (on a TPU that einsum re-lays
the buffers again: the rule says no only where the kernel cannot run).

**A ring** is the buffer of a layer that attends over a window: it has
``window`` places a slot, not ``positions``, and position ``p`` lies at
place ``p mod window`` (:func:`write_rows` and :func:`write_slot` with
``ring``), so a place holds the latest position congruent to it.  Keys are
cached after their rotary, and a softmax does not care in which order the
places lie: the reader is the one a whole buffer has, told to read
:func:`ring_upto` places.  A row that joins a slot another row wrapped
reads no stale place: below ``window`` positions it reads the places it
wrote itself, and by the time it reads them all it has written them all.

**A latent row** (latent attention, MLA) is keys and values at once: a
layer keeps one buffer, a place is ``[c_kv | k_rope]`` in whole lanes (576
values in 640), every query head of a slot reads the same row, and the
values are the row's first ``values`` lanes.  :func:`stored_shape`,
:func:`write_rows` and :func:`write_slot` take such a row as they take any
other (``kv = 1``); :func:`latent_attention` reads it.  With a hundred and
more query heads on one row the products are no plain stream any more (at
128 heads of 576 a place does 242 operations a byte, the chip's ridge), and
a grid step costs half a microsecond fetched or skipped, so this reader
walks a slot's places as the per-head reader does, and did so first (PR
42): one grid step a slot, the buffer left in HBM, a slot's places copied
in chunks up to its length (:func:`latent_places`), a few chunks a copy,
and no place past a row's last chunk fetched or multiplied
(:func:`latent_tile_rule`; PERF.md §5).

**Precision**: queries and probabilities enter the products in the type
the buffers hold (bfloat16 in every program of a voice); scores, the
softmax and every sum are float32.  The online softmax sums in another
order than a softmax over the whole row, no more.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import lanes

F32 = jnp.float32
LANES = 128
#: what stands for "not seen" in the kernel's scores: finite, so that a
#: running maximum of nothing seen yet gives exp(0) and not a NaN
MASKED = -1e30
#: the places of a chunk of keys (and of one of values), what a slot's length
#: is rounded up to: a lane group of scores.  Of the places the reader moves
#: so, the cells' rows hold 0.85 at the mean, 0.74-0.77 at 256, which reads
#: a tenth slower at 1024 lanes a place and no faster at 512 (PERF.md §5 has
#: the table)
CHUNK = 128
#: the most chunks one pass of the online softmax takes: a pass costs about
#: half a microsecond a lane group whatever it holds, so 4 chunks a trip
#: beat 2 by a fifth to a quarter
TRIP_CHUNKS = 4
#: the trips the reader holds in VMEM, of keys and of values: one under the
#: products and two copies ahead of it (with one ahead the copies stall at
#: a slot's end: a tenth to a sixth slower)
BUFFERS = 3
#: the most query rows a lane group may hold: above it the products are no
#: stream of keys and values any more and XLA's own stay
MAX_ROWS = 256
#: the elements of a chunk of latent rows, what a slot's length is rounded
#: up to: 128 places of 640 lanes (160 KB of bfloat16), a lane group of
#: scores.  Of the places the reader moves so, the cell's rows hold 0.84 at
#: the mean, 0.73 at chunks of 256 (PERF.md §5 has the table)
LATENT_CHUNK_ELEMENTS = 128 * 640
#: the most chunks one copy brings in and one pass of the online softmax
#: takes: a pass costs about half a microsecond whatever it holds, so 4
#: chunks a trip beat 2 (and 8 won nothing more)
LATENT_TRIP_CHUNKS = 4
#: the trips the latent reader holds in VMEM: one under the products and two
#: copies ahead of it (with one ahead the copies stall at every slot's end;
#: three ahead won nothing more)
LATENT_BUFFERS = 3


class Tiles(NamedTuple):
    tp: int     #: places of a chunk


def stored_shape(slots: int, positions: int, kv: int, d: int) -> tuple:
    """The shape of a buffer that holds ``[kv, d]`` a slot and place (one
    layer's keys or values; the experts a token chose, ``[layers, k]``):
    a place is one row of whole lanes.  A row narrower than the lanes is
    padded to them: left narrow, the compiler stores the buffer with the
    places on the lanes and re-lays all of it around every write."""
    return (slots, positions, lanes(kv * d))


def _rows_of(buf, new, lead: int):
    """``new`` ``[*lead dims, kv, d]`` as rows of ``buf``'s width."""
    rows = new.reshape(*new.shape[:lead], -1).astype(buf.dtype)
    pad = buf.shape[-1] - rows.shape[-1]
    return jnp.pad(rows, ((0, 0),) * lead + ((0, pad),)) if pad else rows


def write_rows(buf, new, pos, *, ring: bool = False):
    """``new`` ``[S, b, kv, d]`` at the places ``pos`` ``[S, b]`` of every
    slot; in a ``ring`` (the module's docstring) position ``pos`` lies at
    place ``pos`` modulo the buffer's places."""
    rows = jnp.arange(pos.shape[0])[:, None]
    if ring:
        pos = pos % buf.shape[1]
    return buf.at[rows, pos].set(_rows_of(buf, new, 2))


def write_slot(buf, seq, slot, *, ring_n=None):
    """A row's prompt ``seq`` ``[T, kv, d]`` at the places ``0 .. T`` of
    ``slot``.  ``ring_n`` (a ring takes it: the prompt's real length, ``T``
    its padded one): a prompt longer than the ring leaves, at every place,
    the last of its ``ring_n`` positions that falls there."""
    rows = _rows_of(buf, seq, 1)
    window = buf.shape[1]
    if ring_n is not None and rows.shape[0] > window:
        place = jnp.arange(window)
        # a place no real position falls on takes any row: it is not read
        # before a step writes it (``ring_upto``)
        rows = rows[jnp.clip(
            place + (ring_n - 1 - place) // window * window, 0,
            rows.shape[0] - 1)]
    return lax.dynamic_update_slice(buf, rows[None], (slot, 0, 0))


def ring_upto(upto, window: int):
    """How far a reader reads in a ring of ``window`` places for a slot
    whose row holds ``upto`` positions: all of them until the ring is full,
    then every place (the ``window`` latest positions, in the order they
    lie: a softmax does not care)."""
    return jnp.minimum(upto, window)


def read_slot(buf, kv: int, d: int):
    """A slot's rows ``[P, width]`` (on the host or the device) as ``[P,
    kv, d]``."""
    return buf[:, :kv * d].reshape(buf.shape[0], kv, d)


def places_moved(upto, chunk: int):
    """The places a walking reader moves for a slot that holds ``upto``:
    whole chunks of ``chunk`` up to the one its last place lies in (an int,
    or an array a slot).  The kernels' copies and trip counts are made of
    this, and a step group's ``kv_places_fetched`` and
    ``latent_places_fetched`` sum it."""
    return (upto + chunk - 1) // chunk * chunk


@functools.lru_cache(maxsize=None)
def tile_rule(positions: int, kv: int, g: int, d: int,
              b: int) -> Optional[Tiles]:
    """The kernel's chunk (``Tiles.tp``: the places a slot's length is
    rounded up to) for ``b`` queries a slot of ``kv`` heads of ``d`` (``g``
    query heads each) over ``positions`` places, or None where the einsum
    stays: a pure function of the shape.

    The kernel wants whole lanes: a head that fills lane groups (``d`` a
    multiple of 128) or heads that share one (``d`` divides 128 and the
    group's heads divide ``kv``).  The chunk is ``CHUNK`` places, at most
    all the positions, which it has to divide, in whole lane groups (a
    chunk's scores are whole lanes)."""
    if d % LANES and (LANES % d or kv % (LANES // d)):
        return None
    if _sharing(d) * b * g > MAX_ROWS:
        return None
    tp = min(CHUNK, positions)
    return Tiles(tp) if tp % LANES == 0 and positions % tp == 0 else None


def _sharing(d: int) -> int:
    """Heads of keys and values that share a lane group."""
    return max(1, LANES // d)


def _kernel(upto, q_ref, k_rows, v_rows, o_ref, k_trip, v_trip, sem, cursor,
            m_ref, l_ref, acc_ref, *, tp: int, scale: float):
    """One slot a grid step, and in it a loop over the slot's places as far
    as ``upto`` says: a trip takes up to ``TRIP_CHUNKS`` chunks of ``tp``
    places, a copy of keys and one of values a chunk, and one pass of the
    online softmax for each lane group of heads.  ``k_rows`` and ``v_rows``
    are the whole buffers where they lie (HBM); ``k_trip`` and ``v_trip``
    ``[BUFFERS, span, width]`` (``span``: the places of a whole trip) take
    the trips in turn.  The copies run ``BUFFERS - 1`` trips ahead of the
    products, in the order the products take them and from one slot into
    the next: ``cursor`` (SMEM, kept from grid step to grid step) holds the
    next trip to copy (its slot, its number there) and how many trips have
    been multiplied.  ``m_ref`` and ``l_ref`` hold a row's number in every
    lane (a column would be spread again for every use).

    What a start pays for is this function's equations, traced once a
    process and lowered once a program (PERF.md section 6, PR 45): the one
    thing of static size is a pass's places (a body for each count of
    chunks); the chunks' copies and waits are loops, the lane groups one
    batched product, and the scalars are ``lax`` operations on numbers
    that are never negative, not ``jnp``'s with their sign rules."""
    s, slots = pl.program_id(0), pl.num_programs(0)
    groups, rows, lw = q_ref.shape
    buffers, span, _ = k_trip.shape
    ahead, most = buffers - 1, span // tp
    n = upto[s]

    def chunks(holds, t):
        """The chunks trip ``t`` of a slot that holds ``holds`` takes."""
        return jnp.minimum(lax.div(holds - t * span + (tp - 1), tp), most)

    def copies(slot, t, k, c):
        """Chunk ``c`` of trip ``t`` of ``slot`` into buffer ``k``: its
        keys, its values."""
        at = pl.ds(pl.multiple_of(t * span + c * tp, tp), tp)
        to = pl.ds(pl.multiple_of(c * tp, tp), tp)
        return [pltpu.make_async_copy(rows_ref.at[slot, at],
                                      trip.at[k, to], sem.at[i, k])
                for i, (rows_ref, trip) in enumerate(((k_rows, k_trip),
                                                      (v_rows, v_trip)))]

    def held(slot):
        """The first slot from ``slot`` on that holds a place."""
        return lax.while_loop(
            lambda j: jnp.logical_and(
                j < slots, upto[jnp.minimum(j, slots - 1)] == 0),
            lambda j: j + 1, slot)

    def copy_next(k):
        """Start the copies of the trip the cursor stands at, into buffer
        ``k``, and move the cursor on."""
        slot, t = cursor[0], cursor[1]

        @pl.when(slot < slots)
        def _start():
            holds = upto[slot]

            def start(c, carry):
                for copy in copies(slot, t, k, c):
                    copy.start()
                return carry

            lax.fori_loop(0, chunks(holds, t), start, 0)
            last = (t + 1) * span >= holds
            cursor[0] = lax.select(last, held(slot + 1), slot)
            cursor[1] = lax.select(last, 0, t + 1)

    @pl.when(s == 0)
    def _first():
        cursor[0], cursor[1], cursor[2] = held(0), 0, 0

        def first(k, carry):
            copy_next(k)
            return carry

        lax.fori_loop(0, ahead, first, 0)

    m_ref[...] = jnp.full(m_ref.shape, MASKED, F32)
    l_ref[...] = jnp.zeros(l_ref.shape, F32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def trip(t, done):
        k = lax.rem(done, buffers)
        copy_next(lax.rem(done + ahead, buffers))
        count = chunks(n, t)

        def arrived(c, carry):
            for copy in copies(s, t, k, c):
                copy.wait()
            return carry

        lax.fori_loop(0, count, arrived, 0)

        def attend(places):
            """One pass of the online softmax over the trip's ``places``,
            every lane group in one batched product: the equations of one
            group whatever the groups, and all the groups' chains side by
            side for the scheduler as if they were written out."""
            keys, values = (jnp.stack([
                trip[k, :places, j * lw:(j + 1) * lw] for j in range(groups)])
                for trip in (k_trip, v_trip))
            seen = t * span + lax.broadcasted_iota(
                jnp.int32, (1, rows, places), 2) < n
            scores = lax.dot_general(
                q_ref[...], keys, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=F32) * scale
            scores = jnp.where(seen, scores, MASKED)
            m_prev = m_ref[...]
            m_next = jnp.maximum(m_prev,
                                 jnp.max(scores, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(scores - jnp.tile(m_next, (1, 1, places // LANES)))
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2,
                                                      keepdims=True)
            m_ref[...] = m_next
            acc_ref[...] = jnp.tile(alpha, (1, 1, lw // LANES)) \
                * acc_ref[...] + lax.dot_general(
                    p.astype(values.dtype), values,
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=F32)

        # a body of static size for each count of chunks a trip can hold
        for j in range(1, most + 1):
            pl.when(count == j)(functools.partial(attend, j * tp))
        return done + 1

    cursor[2] = lax.fori_loop(0, lax.div(n + (span - 1), span), trip,
                              cursor[2])
    # a slot that sees nothing gives zeros
    total = l_ref[...]
    o_ref[...] = acc_ref[...] / jnp.tile(
        jnp.where(total > 0.0, total, 1.0), (1, 1, lw // LANES))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def slot_attention_kernel(q, k_buf, v_buf, upto, tiles: Tiles, *,
                          interpret: bool = False):
    """The kernel itself, whatever the backend (``interpret`` for the
    CPU): ``q`` ``[S, b, kv, g, d]``, the buffers ``[S, P, kv * d]``,
    ``upto`` ``[S]``.  Returns ``[S, b, kv, g, d]`` float32.  A jitted
    function of its own: a program's layers of one geometry share one trace
    and one lowering of the kernel and its bodies."""
    s, b, kv, g, d = q.shape
    span, width = k_buf.shape[1:]
    tp, heads = tiles.tp, _sharing(d)
    lw = heads * d
    groups = kv // heads
    if width != kv * d or kv % heads or lw % LANES or tp % LANES \
            or span % tp or v_buf.shape != k_buf.shape:
        raise ValueError(f"q {q.shape} and tiles {tiles} do not fit buffers "
                         f"{k_buf.shape}, {v_buf.shape}")
    # a lane group's rows: (head, query, query head), each head's queries
    # in that head's lanes and zeros in the others'
    rows = heads * b * g
    qg = q.reshape(s, b, groups, heads, g, d).transpose(0, 2, 3, 1, 4, 5)
    own = jnp.eye(heads, dtype=q.dtype)
    qg = (qg[:, :, :, :, :, None, :] * own[:, None, None, :, None]).reshape(
        s, groups, rows, lw).astype(k_buf.dtype)
    pad = -rows % 16
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, pad), (0, 0)))
    padded = rows + pad
    # the places of a trip: whole chunks that divide the positions
    trip_places = tp * max(j for j in range(1, TRIP_CHUNKS + 1)
                           if span % (j * tp) == 0)

    def q_map(i, upto):
        return (i, 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, tp=tp, scale=float(d) ** -0.5),
        out_shape=jax.ShapeDtypeStruct((s, groups, padded, lw), F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s,),
            in_specs=[pl.BlockSpec((None, groups, padded, lw), q_map),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, groups, padded, lw), q_map),
            scratch_shapes=[
                pltpu.VMEM((BUFFERS, trip_places, width), k_buf.dtype),
                pltpu.VMEM((BUFFERS, trip_places, width), v_buf.dtype),
                pltpu.SemaphoreType.DMA((2, BUFFERS)),
                pltpu.SMEM((3,), jnp.int32),
                pltpu.VMEM((groups, padded, LANES), F32),
                pltpu.VMEM((groups, padded, LANES), F32),
                pltpu.VMEM((groups, padded, lw), F32)]),
        # the copies run ahead from one slot into the next: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * s * groups * padded * span * lw,
            transcendentals=s * groups * padded * span,
            bytes_accessed=(2 * s * span * width * k_buf.dtype.itemsize
                            + 6 * s * groups * padded * lw)),
        name="slot_attention",
        interpret=interpret,
    )(upto.astype(jnp.int32), qg, k_buf, v_buf)
    out = out[:, :, :rows].reshape(s, groups, heads, b, g, heads, d)
    out = jnp.stack([out[:, :, h, :, :, h] for h in range(heads)], 2)
    return out.transpose(0, 3, 1, 2, 4, 5).reshape(s, b, kv, g, d)


def slot_attention_einsum(q, k_buf, v_buf, upto):
    """The products and the softmax as one expression over the whole
    buffers: what the kernel is held to, and the reader wherever the kernel
    is not."""
    s, b, kv, g, d = q.shape
    span = k_buf.shape[1]
    k = k_buf[..., :kv * d].reshape(s, span, kv, d)
    v = v_buf[..., :kv * d].reshape(s, span, kv, d)
    scores = jnp.einsum("sbkgd,spkd->skgbp", q.astype(k.dtype), k,
                        preferred_element_type=F32) / jnp.sqrt(F32(d))
    seen = jnp.arange(span)[None, :] < upto[:, None]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, None, None, :], scores, -jnp.inf), -1)
    out = jnp.einsum("skgbp,spkd->sbkgd", probs.astype(v.dtype), v,
                     preferred_element_type=F32)
    return jnp.where((upto > 0)[:, None, None, None, None], out, 0.0)


# ---------------------------------------------------------------------------
# a latent row: keys and values at once
# ---------------------------------------------------------------------------

#: the latent reader's name for the places it moves
latent_places = places_moved


@functools.lru_cache(maxsize=None)
def latent_tile_rule(positions: int, g: int, width: int, values: int,
                     b: int) -> Optional[Tiles]:
    """The latent reader's chunk (``Tiles.tp``: the places a slot's length
    is rounded up to) for ``b`` queries a slot of ``g`` heads over
    ``positions`` rows of ``width`` (stored in whole lanes) whose first
    ``values`` lanes are the values, or None where the einsum stays: a pure
    function of the shape, as :func:`tile_rule`.

    The values have to be whole lanes of the row; the query rows of a slot
    (``b * g``) at most ``MAX_ROWS``; the chunk holds
    ``LATENT_CHUNK_ELEMENTS`` of the stored row (the power of two below:
    128 places of 640 lanes), at most all the positions, which it has to
    divide, in whole lane groups (a chunk's scores are whole lanes)."""
    if values % LANES or not 0 < values <= width or b * g > MAX_ROWS:
        return None
    tp = 1 << (max(LATENT_CHUNK_ELEMENTS // lanes(width), 1).bit_length() - 1)
    tp = min(tp, positions)
    return Tiles(tp) if tp % LANES == 0 and positions % tp == 0 else None


def _latent_kernel(upto, q_ref, rows_ref, o_ref, trip_ref, sem, cursor,
                   m_ref, l_ref, acc_ref, *, tp: int, scale: float):
    """One slot a grid step, and in it a loop over the slot's places as far
    as ``upto`` says: a trip takes up to ``LATENT_TRIP_CHUNKS`` chunks of
    ``tp`` places in one copy and one pass of the online softmax (a body
    of static size for each count).  ``rows_ref`` is the whole buffer
    where it lies (HBM); ``trip_ref`` ``[LATENT_BUFFERS, span, stored]``
    (``span``: the places of a whole trip) takes the trips in turn.  The copies run ``LATENT_BUFFERS - 1`` trips
    ahead of the products, in the order the products take them and from
    one slot into the next: ``cursor`` (SMEM, kept from grid step to grid
    step) holds the next trip to copy (its slot, its number there) and how
    many trips have been multiplied.  ``m_ref`` and ``l_ref`` hold a row's
    number in every lane (a column would be spread again for every use)."""
    s, slots = pl.program_id(0), pl.num_programs(0)
    rows, values = o_ref.shape
    buffers, span, _ = trip_ref.shape
    ahead, most = buffers - 1, span // tp
    n = upto[s]

    def chunks(holds, t):
        """The chunks trip ``t`` of a slot that holds ``holds`` takes."""
        return jnp.minimum(latent_places(holds - t * span, tp) // tp, most)

    def sized(count, body):
        """``body(places)`` at the static size ``count`` chunks have."""
        for j in range(1, most + 1):
            pl.when(count == j)(functools.partial(body, j * tp))

    def copy(slot, t, k, places):
        return pltpu.make_async_copy(
            rows_ref.at[slot, pl.ds(pl.multiple_of(t * span, span), places)],
            trip_ref.at[k, pl.ds(0, places)], sem.at[k])

    def held(slot):
        """The first slot from ``slot`` on that holds a place."""
        return lax.while_loop(
            lambda j: jnp.logical_and(
                j < slots, upto[jnp.minimum(j, slots - 1)] == 0),
            lambda j: j + 1, slot)

    def copy_next(k):
        """Start the copy of the trip the cursor stands at, into buffer
        ``k``, and move the cursor on."""
        slot, t = cursor[0], cursor[1]

        @pl.when(slot < slots)
        def _start():
            holds = upto[slot]
            sized(chunks(holds, t),
                  lambda places: copy(slot, t, k, places).start())
            last = (t + 1) * span >= holds
            cursor[0] = jnp.where(last, held(slot + 1), slot)
            cursor[1] = jnp.where(last, 0, t + 1)

    @pl.when(s == 0)
    def _first():
        cursor[0], cursor[1], cursor[2] = held(0), 0, 0
        for k in range(ahead):
            copy_next(k)

    m_ref[...] = jnp.full(m_ref.shape, MASKED, F32)
    l_ref[...] = jnp.zeros(l_ref.shape, F32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def trip(t, done):
        k = done % buffers
        copy_next((done + ahead) % buffers)

        def attend(places):
            copy(s, t, k, places).wait()
            c = trip_ref[k, :places]
            seen = t * span + lax.broadcasted_iota(
                jnp.int32, (rows, places), 1) < n
            scores = lax.dot_general(
                q_ref[...], c, (((1,), (1,)), ((), ())),
                preferred_element_type=F32) * scale
            scores = jnp.where(seen, scores, MASKED)
            m_prev = m_ref[...]
            m_next = jnp.maximum(m_prev,
                                 jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(scores - jnp.tile(m_next, (1, places // LANES)))
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            m_ref[...] = m_next
            acc_ref[...] = jnp.tile(alpha, (1, values // LANES)) \
                * acc_ref[...] + jnp.dot(p.astype(c.dtype), c[:, :values],
                                         preferred_element_type=F32)

        sized(chunks(n, t), attend)
        return done + 1

    cursor[2] = lax.fori_loop(0, latent_places(n, span) // span, trip,
                              cursor[2])
    # a slot that sees nothing gives zeros
    total = l_ref[...]
    o_ref[...] = (acc_ref[...] / jnp.tile(
        jnp.where(total > 0.0, total, 1.0),
        (1, values // LANES))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("values", "scale", "tiles",
                                             "interpret"))
def latent_attention_kernel(q, buf, upto, values: int, scale: float,
                            tiles: Tiles, *, interpret: bool = False):
    """The latent reader's kernel, whatever the backend (``interpret`` for
    the CPU): ``q`` ``[S, b, g, width]``, the buffer ``[S, P, lanes(width)]``,
    ``upto`` ``[S]``.  Returns ``[S, b, g, values]`` in the buffer's type
    (the float32 quotient rounded once, as its caller would).  A jitted
    function of its own: a program's layers share one trace and one
    lowering of the kernel, whose four bodies a layer would else add a
    fifth to a warm start (PERF.md §6 PR 42)."""
    s, b, g, width = q.shape
    span, stored = buf.shape[1:]
    tp = tiles.tp
    if stored != lanes(width) or values % LANES or values > width \
            or tp % LANES or span % tp:
        raise ValueError(f"q {q.shape}, values {values} and tiles {tiles} "
                         f"do not fit the buffer {buf.shape}")
    rows = b * g
    padded = rows + -rows % 16
    qg = jnp.pad(q.reshape(s, rows, width).astype(buf.dtype),
                 ((0, 0), (0, padded - rows), (0, stored - width)))
    # the places of a trip: whole chunks that divide the positions
    trip_places = tp * max(j for j in range(1, LATENT_TRIP_CHUNKS + 1)
                           if span % (j * tp) == 0)

    def q_map(i, upto):
        return (i, 0, 0)

    out = pl.pallas_call(
        functools.partial(_latent_kernel, tp=tp, scale=scale),
        out_shape=jax.ShapeDtypeStruct((s, padded, values), buf.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s,),
            in_specs=[pl.BlockSpec((None, padded, stored), q_map),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, padded, values), q_map),
            scratch_shapes=[pltpu.VMEM((LATENT_BUFFERS, trip_places,
                                        stored), buf.dtype),
                            pltpu.SemaphoreType.DMA((LATENT_BUFFERS,)),
                            pltpu.SMEM((3,), jnp.int32),
                            pltpu.VMEM((padded, LANES), F32),
                            pltpu.VMEM((padded, LANES), F32),
                            pltpu.VMEM((padded, values), F32)]),
        # the copies run ahead from one slot into the next: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * s * padded * span * (stored + values),
            transcendentals=s * padded * span,
            bytes_accessed=buf.dtype.itemsize * s * (
                span * stored + padded * (stored + values))),
        name="latent_attention",
        interpret=interpret,
    )(upto.astype(jnp.int32), qg, buf)
    return out[:, :rows].reshape(s, b, g, values)


def latent_attention_einsum(q, buf, upto, values: int, scale: float):
    """The products and the softmax as one expression over the whole
    buffer: what the kernel is held to, and the reader wherever the kernel
    is not."""
    width = q.shape[-1]
    span = buf.shape[1]
    scores = jnp.einsum("sbgw,spw->sgbp", q.astype(buf.dtype),
                        buf[..., :width],
                        preferred_element_type=F32) * F32(scale)
    seen = jnp.arange(span)[None, :] < upto[:, None]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, None, :], scores, -jnp.inf), -1)
    out = jnp.einsum("sgbp,spv->sgbv", probs.astype(buf.dtype),
                     buf[..., :values], preferred_element_type=F32)
    return jnp.where((upto > 0)[:, None, None, None],
                     out.transpose(0, 2, 1, 3), 0.0)


def _latent_tiles_here(positions: int, g: int, width: int, values: int,
                       b: int) -> Optional[Tiles]:
    """``latent_tile_rule``'s tiles on a TPU, None on every other
    backend."""
    if jax.default_backend() != "tpu":
        return None
    return latent_tile_rule(positions, g, width, values, b)


def latent_implementation(positions: int, g: int, width: int, values: int,
                          b: int) -> str:
    """:func:`implementation` for :func:`latent_attention`."""
    return ("einsum" if _latent_tiles_here(positions, g, width, values, b)
            is None else "slot_kernel")


def latent_attention(q, buf, upto, values: int, scale: float):
    """Every slot's queries ``q`` ``[S, b, g, width]`` over the slot's
    latent rows at the places ``< upto[slot]``, the scores times ``scale``
    (the model's, not the row's width): ``[S, b, g, values]`` in the rows'
    type, the probabilities' sums of the rows' first ``values`` lanes
    (float32 sums, rounded once); zeros for a slot that sees nothing."""
    _, b, g, width = q.shape
    tiles = _latent_tiles_here(buf.shape[1], g, width, values, b)
    if tiles is None:
        return latent_attention_einsum(q, buf, upto, values,
                                       scale).astype(buf.dtype)
    return latent_attention_kernel(q, buf, upto, values, scale, tiles)


def latent_reach(positions: int, g: int, width: int, values: int,
                 b: int) -> int:
    """What :func:`latent_attention` rounds a slot's length up to on this
    backend (:func:`latent_places`' chunk): the kernel's chunk, or every
    position where the einsum reads."""
    tiles = _latent_tiles_here(positions, g, width, values, b)
    return positions if tiles is None else tiles.tp


def _tiles_here(positions: int, kv: int, g: int, d: int,
                b: int) -> Optional[Tiles]:
    """``tile_rule``'s tiles on a TPU, None on every other backend."""
    if jax.default_backend() != "tpu":
        return None
    return tile_rule(positions, kv, g, d, b)


def implementation(positions: int, kv: int, g: int, d: int, b: int) -> str:
    """``"slot_kernel"`` where :func:`slot_attention` runs the kernel at
    this shape on this backend, else ``"einsum"``: what the spans report."""
    return ("einsum" if _tiles_here(positions, kv, g, d, b) is None
            else "slot_kernel")


def reach(positions: int, kv: int, g: int, d: int, b: int) -> int:
    """What :func:`slot_attention` rounds a slot's length up to on this
    backend (:func:`places_moved`' chunk): the kernel's chunk, or every
    place of the buffer where the einsum reads."""
    tiles = _tiles_here(positions, kv, g, d, b)
    return positions if tiles is None else tiles.tp


def slot_attention(q, k_buf, v_buf, upto):
    """Every slot's queries ``q`` ``[S, b, kv, g, d]`` over the slot's
    keys and values at the places ``< upto[slot]`` (all ``b`` queries of a
    slot see the same places): ``[S, b, kv, g, d]`` float32; zeros for a
    slot that sees nothing."""
    _, b, kv, g, d = q.shape
    tiles = _tiles_here(k_buf.shape[1], kv, g, d, b)
    if tiles is None:
        return slot_attention_einsum(q, k_buf, v_buf, upto)
    return slot_attention_kernel(q, k_buf, v_buf, upto, tiles)
