#!/usr/bin/env python3
"""A step's attention over the slots alone, on the chip: the write of a
step's places and the read of every slot's keys and values, by each
candidate layout and reader, at the shapes the unit voices' steps run.

    python tools/profile_attention.py [--only PREFIX] [--layouts] [--out F]
                                      [--rehearse]

Candidates (``sonata_tpu/ops/slot_attention.py`` says which the programs
run): ``einsum@SPw``, the einsum over the stored layout ``[S, P, kv * d]``
(what runs off a TPU); ``grid(tp)``, the reader until PR 45 (a grid of
(slot, tile of ``tp`` places), kept here to be measured against: first the
tile its rule gave the shape); ``walk(chunk, most, buffers)``,
``slot_attention_kernel`` (one grid step a slot, the slot's keys and values
copied in chunks up to its length, at most ``most`` chunks a trip,
``buffers`` trips in VMEM; the first is the rule's); with ``--layouts`` also
``einsum@SPkd``, buffers ``[S, P, kv, d]`` written by a scatter and read by
two einsums (every program's until PR 37), and ``einsum@SkPd``, the same
over ``[S, kv, P, d]`` (both products batched over slot and head).  Every
candidate writes ``b`` places a slot and then attends, on buffers it is
given to keep (donated), ``REPS`` layers in one jitted program, as a step
does; a reading is the host's clock around that program over ``REPS``, the
least of five; ``kernel_ms`` is the device time of the kernel's own
operations a layer, from a capture of two more programs.  ``upto`` is drawn
as the cells' rows stand: a prompt of 68-182 ids and a uniform share of its
``3.5 x ids`` units behind it (68-819 places, 344 at the mean; a ring of 512
places is read as far as ``ring_upto`` says).  ``GB/s`` counts what
``perfbench/harness/lfm2_costs.py`` charges a step: a key and a value of
``kv * d`` for every place a slot attends over, bfloat16; percent is of 819
GB/s; ``places_fetched`` is what the candidate moves (``places_moved`` of
``upto`` at its chunk or tile; every place for an einsum).  Every candidate
is held to the first.  Needs a TPU (``--rehearse``: the CPU, a few slots
and places, the kernels interpreted: no times).

``--latent`` measures the latent reader instead (``pangu_step``: 256 slots,
128 query heads on one row of 576 values in 640 lanes, the values its first
512): ``einsum@SPw``; ``grid(tp)``, the reader until PR 42 (a grid of (slot,
tile of ``tp`` places), kept here to be measured against: at 1024 what PR 41
ran); ``walk(chunk, most, buffers)``, ``latent_attention_kernel`` (one grid
step a slot, the slot's places copied in chunks up to its length, at most
``most`` chunks a trip, ``buffers`` trips in VMEM; the first is the rule's),
each a write of the step's row and the read, ``REPS`` layers a program.  ``GB/s`` counts one row of 576 values a place attended over;
``ms_of_ops`` is the two products at the MXU's 197 TFLOP/s beside
``ms_of_bytes``: this reader sits at the ridge.  ``places_fetched`` is what
the candidate moves (``latent_places`` of ``upto`` at its chunk or tile;
every place for the einsum), ``kernel_ms`` the device time of the kernel's
own operations a layer, from a capture of two more programs (a reading by
the host's clock holds the queries' padding and the scatter as well).
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sa = importlib.import_module("sonata_tpu.ops.slot_attention")

REPS = 4
BF16, F32 = jnp.bfloat16, jnp.float32
#: name -> (S, P, kv, g, d, b): the four cells' step programs (the last
#: two are one program's: a whole cache and a ring of 512 places)
SHAPES = {
    "lfm2_step": (64, 1024, 8, 4, 64, 1),
    "sdar_pass": (64, 1024, 4, 8, 128, 4),
    "nemotron_step": (256, 1024, 2, 16, 128, 1),
    "laguna_full": (256, 1024, 8, 6, 128, 1),
    "laguna_ring": (256, 512, 8, 8, 128, 1),
}
TILES = (128, 256, 512, 1024)
#: the elements of a tile of keys under the rule until PR 45
GRID_ELEMENTS = 512 * 512
#: the walking kernel's candidates: (places a chunk, chunks a trip at
#: most, trips in VMEM); the first is the rule's
SLOT_WALKS = ((128, 4, 3), (128, 2, 3), (256, 2, 3), (128, 4, 2))


def draw_upto(rng, slots: int, positions: int, b: int):
    ids = rng.integers(68, 183, slots)
    at = ids + (rng.random(slots) * 3.5 * ids).astype(np.int64)
    return np.clip(at // b * b + b, b, positions).astype(np.int32)


def einsums(buffers: str, q, k_buf, v_buf, upto):
    """The two einsums ``unit_layers.attn_op_step`` and ``sdar.attn_op_block``
    had, over buffers whose dimensions ``buffers`` names (``spkd``: as they
    had them)."""
    d, span = q.shape[-1], k_buf.shape[buffers.index("p")]
    scores = jnp.einsum(f"sbkgd,{buffers}->skgbp", q.astype(BF16), k_buf,
                        preferred_element_type=F32) / jnp.sqrt(F32(d))
    seen = (jnp.arange(span)[None, :] < upto[:, None])[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum(f"skgbp,{buffers}->sbkgd", probs.astype(BF16), v_buf,
                      preferred_element_type=F32)


def _slot_grid_kernel(upto, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      acc_ref, *, tp: int, scale: float):
    s, t = pl.program_id(0), pl.program_id(1)
    groups, rows, lw = q_ref.shape
    n = upto[s]

    @pl.when(t == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, sa.MASKED, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    @pl.when(t * tp < n)
    def _tile():
        seen = t * tp + jax.lax.broadcasted_iota(
            jnp.int32, (rows, tp), 1) < n
        for j in range(groups):
            lanes = slice(j * lw, (j + 1) * lw)
            scores = jax.lax.dot_general(
                q_ref[j], k_ref[:, lanes], (((1,), (1,)), ((), ())),
                preferred_element_type=F32) * scale
            scores = jnp.where(seen, scores, sa.MASKED)
            m_prev = m_ref[j]
            m_next = jnp.maximum(m_prev,
                                 jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(scores - m_next)
            l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=1, keepdims=True)
            m_ref[j] = m_next
            acc_ref[j] = alpha * acc_ref[j] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[:, lanes],
                preferred_element_type=F32)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        # a slot that sees nothing gives zeros
        total = l_ref[...]
        o_ref[...] = acc_ref[...] / jnp.where(total > 0.0, total, 1.0)


def slot_grid(q, k_buf, v_buf, upto, tp: int, *, interpret: bool = False):
    """The per-head reader until PR 45: a grid of (slot, tile of ``tp``
    places), one block of keys and one of values a tile, a tile past a
    slot's length neither fetched (its block index repeats) nor multiplied,
    its grid step paid."""
    s, b, kv, g, d = q.shape
    span, width = k_buf.shape[1:]
    heads = sa._sharing(d)
    lw = heads * d
    groups = kv // heads
    if width != kv * d or kv % heads or lw % sa.LANES or span % tp:
        raise ValueError(f"q {q.shape} and tiles of {tp} do not fit buffers "
                         f"{k_buf.shape}")
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

    def q_map(i, t, upto):
        return (i, 0, 0, 0)

    def kv_map(i, t, upto):
        # past the slot's last tile the index stays: nothing is fetched
        return (i, jnp.minimum(t, (jnp.maximum(upto[i], 1) - 1) // tp), 0)

    out = pl.pallas_call(
        functools.partial(_slot_grid_kernel, tp=tp, scale=float(d) ** -0.5),
        out_shape=jax.ShapeDtypeStruct((s, groups, padded, lw), F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, span // tp),
            in_specs=[pl.BlockSpec((None, groups, padded, lw), q_map),
                      pl.BlockSpec((None, tp, width), kv_map),
                      pl.BlockSpec((None, tp, width), kv_map)],
            out_specs=pl.BlockSpec((None, groups, padded, lw), q_map),
            scratch_shapes=[pltpu.VMEM((groups, padded, 1), F32),
                            pltpu.VMEM((groups, padded, 1), F32),
                            pltpu.VMEM((groups, padded, lw), F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * s * groups * padded * span * lw,
            transcendentals=s * groups * padded * span,
            bytes_accessed=(2 * s * span * width * k_buf.dtype.itemsize
                            + 6 * s * groups * padded * lw)),
        name="slot_grid",
        interpret=interpret,
    )(upto.astype(jnp.int32), qg, k_buf, v_buf)
    out = out[:, :, :rows].reshape(s, groups, heads, b, g, heads, d)
    out = jnp.stack([out[:, :, h, :, :, h] for h in range(heads)], 2)
    return out.transpose(0, 3, 1, 2, 4, 5).reshape(s, b, kv, g, d)



def walk(kernel, constants: dict, *args, **kwargs):
    """A walking kernel of the module (``slot_attention_kernel``,
    ``latent_attention_kernel``) with the module's ``constants`` (its chunks
    a trip, its trips in VMEM) set to other values while it is traced."""
    was = {name: getattr(sa, name) for name in constants}
    for name, value in constants.items():
        setattr(sa, name, value)
    try:
        # the function under its jit: the constants are read as it is traced
        return kernel.__wrapped__(*args, **kwargs)
    finally:
        for name, value in was.items():
            setattr(sa, name, value)


def candidates(shape: tuple, rehearse: bool, layouts: bool,
               walks: tuple = SLOT_WALKS) -> list:
    """``(name, stored shape, write, attend, places a copy or a block
    brings in, the kernel's name)``."""
    s, p, kv, g, d, b = shape
    rows = jnp.arange(s)[:, None]
    stored = sa.stored_shape(s, p, kv, d)

    def write_spkd(buf, new, pos):
        return buf.at[rows, pos].set(new)

    def write_skpd(buf, new, pos):
        return buf.at[rows, :, pos].set(new)

    out = [("einsum@SPw", stored, sa.write_rows, sa.slot_attention_einsum,
            p, None)]
    if layouts:
        out += [("einsum@SPkd", (s, p, kv, d), write_spkd,
                 functools.partial(einsums, "spkd"), p, None),
                ("einsum@SkPd", (s, kv, p, d), write_skpd,
                 functools.partial(einsums, "skpd"), p, None)]
    ruled = min(1 << ((GRID_ELEMENTS // (kv * d)).bit_length() - 1), p)
    for tp in (ruled, *(t for t in TILES if t != ruled)):
        if p % tp == 0 and (layouts or tp == ruled):
            out.append((f"grid({tp})", stored, sa.write_rows,
                        functools.partial(slot_grid, tp=tp,
                                          interpret=rehearse), tp,
                        "slot_grid"))
    for tp, most, buffers in walks:
        if p % tp == 0:
            out.append((f"walk({tp}, {most}, {buffers})", stored,
                        sa.write_rows, functools.partial(
                            walk, sa.slot_attention_kernel,
                            {"TRIP_CHUNKS": most, "BUFFERS": buffers},
                            tiles=sa.Tiles(tp), interpret=rehearse), tp,
                        "slot_attention"))
    return out


def build(write, attend):
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def many(k_bufs, v_bufs, qs, ks, vs, pos, upto):
        outs = []
        k_bufs, v_bufs = list(k_bufs), list(v_bufs)
        for i in range(REPS):
            k_bufs[i] = write(k_bufs[i], ks[i], pos)
            v_bufs[i] = write(v_bufs[i], vs[i], pos)
            outs.append(attend(qs[i], k_bufs[i], v_bufs[i], upto))
        return k_bufs, v_bufs, jnp.stack(outs)

    return many


def fill(stored: tuple, shape: tuple, key):
    """A buffer of the stored shape holding the same keys whatever the
    layout: drawn as ``[S, P, kv, d]``."""
    s, p, kv, d, = shape[0], shape[1], shape[2], shape[4]
    flat = jax.random.normal(key, (s, p, kv, d), BF16)
    if stored == (s, kv, p, d):
        return flat.transpose(0, 2, 1, 3)
    return flat.reshape(stored)


def measure(name: str, shape: tuple, seed: int, rehearse: bool,
            layouts: bool = False, walks: tuple = SLOT_WALKS) -> list:
    s, p, kv, g, d, b = shape
    rng = np.random.default_rng(seed)
    # a ring is read as far as it is written, at most all of it
    uptos = [np.minimum(draw_upto(rng, s, 1024, b), p) for _ in range(2)]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3 + 2 * REPS)
    qs = jax.random.normal(keys[0], (REPS, s, b, kv, g, d), F32)
    ks = jax.random.normal(keys[1], (REPS, s, b, kv, d), BF16)
    vs = jax.random.normal(keys[2], (REPS, s, b, kv, d), BF16)
    lines, ref = [], None
    for cand, stored, write, attend, chunk, kernel in candidates(
            shape, rehearse, layouts, walks):
        line = {"shape": name, "S": s, "P": p, "kv": kv, "g": g, "d": d,
                "b": b, "candidate": cand,
                "mean_upto": float(np.mean(uptos)),
                "places_fetched": float(np.mean(
                    [sa.places_moved(u, chunk).sum() for u in uptos]))}
        try:
            fn = build(write, attend)
            k_bufs = [fill(stored, shape, keys[3 + i]) for i in range(REPS)]
            v_bufs = [fill(stored, shape, keys[3 + REPS + i])
                      for i in range(REPS)]

            def run(i):
                nonlocal k_bufs, v_bufs
                upto = jnp.asarray(uptos[i % 2])
                pos = upto[:, None] - b + jnp.arange(b)[None, :]
                k_bufs, v_bufs, out = jax.block_until_ready(
                    fn(k_bufs, v_bufs, qs, ks, vs, pos, upto))
                return out

            times = []
            for i in range(2 if rehearse else 6):
                t0 = time.perf_counter()
                out = run(i)
                times.append(time.perf_counter() - t0)
                if i == 0:
                    line["compile_s"] = times[0]
                    if ref is None:
                        ref = out
                        line["ref_abs_max"] = float(jnp.max(jnp.abs(ref)))
                    line["err_max"] = float(jnp.max(jnp.abs(out - ref)))
            if not rehearse:
                ms = min(times[1:]) * 1e3 / REPS
                charged = 2 * 2 * kv * d * float(np.mean(
                    [u.sum() for u in uptos]))
                line.update(ms=ms, gb_per_s=charged / ms / 1e6,
                            share_of_819=charged / ms / 1e6 / 819.0,
                            ms_of_bytes=charged / 819e6)
                if kernel:
                    line["kernel_ms"] = kernel_ms(
                        lambda: [run(i) for i in range(2)], kernel, 2 * REPS)
            del k_bufs, v_bufs
        except Exception as e:  # a candidate the compiler refuses
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


#: the latent reader's shape: S, P, query heads, a row's values, of which
#: the first are the values
LATENT = (256, 1024, 128, 576, 512)
#: the walking kernel's candidates: (places a chunk, chunks a trip at
#: most, trips in VMEM); the first is the rule's
WALKS = ((128, 4, 3), (256, 4, 3), (256, 2, 3), (512, 1, 3), (128, 2, 3),
         (128, 8, 3), (128, 4, 2), (128, 4, 4))


def _grid_kernel(upto, q_ref, c_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 tp: int, scale: float):
    s, t = pl.program_id(0), pl.program_id(1)
    rows, values = o_ref.shape
    n = upto[s]

    @pl.when(t == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, sa.MASKED, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    @pl.when(t * tp < n)
    def _tile():
        seen = t * tp + jax.lax.broadcasted_iota(
            jnp.int32, (rows, tp), 1) < n
        scores = jax.lax.dot_general(
            q_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=F32) * scale
        scores = jnp.where(seen, scores, sa.MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(c_ref.dtype), c_ref[:, :values],
            preferred_element_type=F32)

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        total = l_ref[...]
        o_ref[...] = acc_ref[...] / jnp.where(total > 0.0, total, 1.0)


def latent_grid(q, buf, upto, values: int, scale: float, tp: int, *,
                interpret: bool = False):
    """The latent reader until PR 42: a grid of (slot, tile of ``tp``
    places), one block of the buffer a tile, a tile past a slot's length
    neither fetched (its block index repeats) nor multiplied, its grid
    step paid."""
    s, b, g, width = q.shape
    span, stored = buf.shape[1:]
    rows = b * g
    padded = rows + -rows % 16
    qg = jnp.pad(q.reshape(s, rows, width).astype(buf.dtype),
                 ((0, 0), (0, padded - rows), (0, stored - width)))

    def q_map(i, t, upto):
        return (i, 0, 0)

    def row_map(i, t, upto):
        return (i, jnp.minimum(t, (jnp.maximum(upto[i], 1) - 1) // tp), 0)

    out = pl.pallas_call(
        functools.partial(_grid_kernel, tp=tp, scale=scale),
        out_shape=jax.ShapeDtypeStruct((s, padded, values), F32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, span // tp),
            in_specs=[pl.BlockSpec((None, padded, stored), q_map),
                      pl.BlockSpec((None, tp, stored), row_map)],
            out_specs=pl.BlockSpec((None, padded, values), q_map),
            scratch_shapes=[pltpu.VMEM((padded, 1), F32),
                            pltpu.VMEM((padded, 1), F32),
                            pltpu.VMEM((padded, values), F32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="latent_grid",
        interpret=interpret,
    )(upto.astype(jnp.int32), qg, buf)
    return out[:, :rows].reshape(s, b, g, values)


def kernel_ms(run, name: str, layers: int):
    """Device milliseconds a layer of the operations named ``name`` while
    ``run()`` runs under a capture of its own, or None (no such operation:
    an einsum has no name of its own)."""
    import shutil
    import tempfile

    from perfbench.harness import trace

    log_dir = tempfile.mkdtemp(prefix="profile_attention_")
    try:
        jax.profiler.start_trace(log_dir)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        events = trace.load_events(
            log_dir, planes=lambda p: bool(trace.DEVICE_PLANE.match(p)))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    mine = [e["dur_ns"] for e in events
            if e["line"] == trace.OPS_LINE and name in e["name"]]
    return sum(mine) / 1e6 / layers if mine else None


def measure_latent(seed: int, rehearse: bool) -> list:
    s, p, g, width, values = (4, 256, 8, 576, 512) if rehearse else LATENT
    scale = 192.0 ** -0.5
    rng = np.random.default_rng(seed)
    uptos = [draw_upto(rng, s, p, 1) for _ in range(2)]
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 + REPS)
    dtype = F32 if rehearse else BF16       # the CPU has no bfloat16 dot
    qs = jax.random.normal(keys[0], (REPS, s, 1, g, width), F32)
    rows = jax.random.normal(keys[1], (REPS, s, 1, 1, width), dtype)
    #: (name, attend, places a copy or a block brings in, the kernel's name)
    cands = [("einsum@SPw", sa.latent_attention_einsum, p, None)] + [
        (f"grid({tp})", functools.partial(latent_grid, tp=tp,
                                          interpret=rehearse), tp,
         "latent_grid")
        for tp in TILES if tp <= p] + [
        (f"walk({tp}, {most}, {buffers})", functools.partial(
            walk, sa.latent_attention_kernel,
            {"LATENT_TRIP_CHUNKS": most, "LATENT_BUFFERS": buffers},
            tiles=sa.Tiles(tp), interpret=rehearse),
         tp, "latent_attention")
        for tp, most, buffers in WALKS if tp <= p]
    lines, ref = [], None
    for cand, attend, chunk, kernel in cands:
        line = {"shape": "pangu_step", "S": s, "P": p, "g": g,
                "width": width, "values": values, "candidate": cand,
                "mean_upto": float(np.mean(uptos)),
                "places_fetched": float(np.mean(
                    [sa.latent_places(u, chunk).sum() for u in uptos]))}

        @functools.partial(jax.jit, donate_argnums=(0,))
        def many(bufs, qs, rows, pos, upto, attend=attend):
            bufs, outs = list(bufs), []
            for i in range(REPS):
                bufs[i] = sa.write_rows(bufs[i], rows[i], pos)
                outs.append(attend(qs[i], bufs[i], upto, values, scale))
            return bufs, jnp.stack(outs)

        try:
            bufs = [jnp.pad(jax.random.normal(keys[2 + i], (s, p, width),
                                              dtype),
                            ((0, 0), (0, 0), (0, -width % 128)))
                    for i in range(REPS)]
            times = []
            for i in range(2 if rehearse else 6):
                upto = jnp.asarray(uptos[i % 2])
                t0 = time.perf_counter()
                bufs, out = jax.block_until_ready(
                    many(bufs, qs, rows, upto[:, None] - 1, upto))
                times.append(time.perf_counter() - t0)
                if i == 0:
                    line["compile_s"] = times[0]
                    if ref is None:
                        ref = out
                        line["ref_abs_max"] = float(jnp.max(jnp.abs(ref)))
                    line["err_max"] = float(jnp.max(jnp.abs(out - ref)))
            if not rehearse:
                ms = min(times[1:]) * 1e3 / REPS
                places = float(np.mean([u.sum() for u in uptos]))
                charged = 2 * width * places
                line.update(ms=ms, gb_per_s=charged / ms / 1e6,
                            share_of_819=charged / ms / 1e6 / 819.0,
                            ms_of_bytes=charged / 819e6,
                            ms_of_ops=2 * g * (width + values) * places
                            / 197e9)
                if kernel:
                    def twice():
                        nonlocal bufs
                        for u in uptos:
                            u = jnp.asarray(u)
                            bufs, _ = jax.block_until_ready(
                                many(bufs, qs, rows, u[:, None] - 1, u))

                    line["kernel_ms"] = kernel_ms(twice, kernel, 2 * REPS)
            del bufs
        except Exception as e:  # a candidate the compiler refuses
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="shapes whose name starts with this alone")
    ap.add_argument("--seed", type=int, default=3700)
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU at 4 slots of 256 places, the kernel "
                         "interpreted: results only, no times")
    ap.add_argument("--layouts", action="store_true",
                    help="the layouts until PR 37 and the grid kernel at "
                         "every tile too")
    ap.add_argument("--walks", nargs="+", metavar="CHUNK,MOST,BUFFERS",
                    help="these candidates of the walking kernel in the "
                         "place of its own list")
    ap.add_argument("--latent", action="store_true",
                    help="the latent reader (one row a place, keys and "
                         "values at once) in the other shapes' place")
    ap.add_argument("--out", default="chiprun_out/profile_attention.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 1
    lines = measure_latent(args.seed, args.rehearse) if args.latent else []
    walks = tuple(tuple(int(n) for n in w.split(","))
                  for w in args.walks) if args.walks else SLOT_WALKS
    for name, shape in SHAPES.items():
        if name.startswith(args.only) and not args.latent:
            if args.rehearse:
                shape = (4, 256) + shape[2:]
            lines += measure(name, shape, args.seed, args.rehearse,
                             args.layouts, walks)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"device": {"platform": device.platform, "kind": device.device_kind},
         "reps": REPS, "lines": lines}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
