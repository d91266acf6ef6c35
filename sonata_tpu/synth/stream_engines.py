"""The stream engines of a stock voice: owners of a batching core, beside
:mod:`.scheduler` (sentence requests) and :mod:`.steploop` (unit voices).

A :class:`~sonata_tpu.models.piper.PiperVoice` streams through three
engines, each handed the voice it drives and holding it weakly:

- :class:`_StreamStageCoalescer`: stream *starts* (encode + acoustics)
  that arrive together become one batched dispatch of each stage;
- :class:`_StreamDecodeCoalescer`: window decodes, gathered wave by wave
  (dispatch mode);
- :class:`_IterationStreamDecoder`: window decodes as rows of the
  persistent :class:`~.batching.IterationLoop` (iteration mode).

The voice builds them lazily (``PiperVoice._stream_decoder`` /
``_stream_stages``), as ``UnitVoice._step_loop`` builds its step loop:
nothing here imports ``models`` at module level.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import OperationError
from ..utils.buckets import FRAME_BUCKETS, TEXT_BUCKETS, bucket_for
from ..utils.transfer import prefetch_to_host
from .batching import (
    BatchingCore,
    IterationLoop,
    WorkItem,
    try_set_exception,
    try_set_result,
)


def _assemble_window_dispatch(v: "PiperVoice", key, payloads: list,
                              b: int):
    """Build one window-decode group's (fn, args) padded to ``b`` rows —
    the ONE place the (window, sid[, lo, hi]) payload layout is
    consumed, shared by both engines so the fused contract cannot
    desynchronize between them."""
    width, has_sid, fused = key
    pad = b - len(payloads)
    windows = jnp.stack([p[0] for p in payloads]
                        + [payloads[0][0]] * pad)
    args = [v.params, windows]
    if fused:
        args += [jnp.asarray([p[2] for p in payloads]
                             + [payloads[0][2]] * pad, jnp.int32),
                 jnp.asarray([p[3] for p in payloads]
                             + [payloads[0][3]] * pad, jnp.int32)]
    if has_sid:
        args.append(jnp.asarray(
            [p[1] for p in payloads] + [payloads[0][1]] * pad,
            dtype=jnp.int32))
    fn = (v._decode_windows_fused_fn(width, b, has_sid) if fused
          else v._decode_windows_batch_fn(width, b, has_sid))
    return fn, args


def _fetch_window_results(out, n: int, fused: bool) -> list:
    """The finisher-side twin: blocking fetch + per-row unpack.  Fused
    results are (i16 row, peak) pairs; plain results f32 rows."""
    if fused:
        q, peaks = jax.device_get(out)
        q, peaks = np.asarray(q), np.asarray(peaks)
        return [(q[i], float(peaks[i])) for i in range(n)]
    return list(np.asarray(jax.device_get(out))[:n])


class _StreamDecodeCoalescer:
    """Shared dispatcher for streaming window decodes (dispatch mode).

    The reference serves each realtime stream from its own blocking thread
    (``grpc/src/main.rs:381-409``), so N concurrent streams contend for
    the device with N independent decode calls per chunk wave.  Here every
    stream's window decode funnels through one queue; the batching core
    groups requests of equal window width (and same z frame-bucket shape)
    that arrive within ``max_wait_ms`` and this class issues ONE batched
    decode — under concurrent load the chunk cost approaches one dispatch
    per wave instead of one per stream, while a lone stream pays only the
    tiny wait window.

    Since the batching-core unification the queue/gather/drain machinery
    lives in :class:`~sonata_tpu.synth.batching.BatchingCore` (two-phase:
    the dispatcher thread enqueues device programs back-to-back while the
    finisher blocks on each async-prefetched result copy — a single
    thread doing both serialized every wave behind the previous wave's
    result fetch); this class keeps only the decode policy.
    """

    def __init__(self, voice: "PiperVoice", *, max_batch: int = 8,
                 max_wait_ms: float = 2.0):
        import weakref

        # weak back-reference: the voice owns the coalescer; a strong ref
        # here would pin the voice (and its params) to this thread's frame
        # for process lifetime
        self._voice_ref = weakref.ref(voice)
        self._max_batch = max_batch
        self._max_wait = max_wait_ms / 1000.0
        self._reason = "stream-decode coalescer closed (voice unloaded)"
        self._core = BatchingCore(
            dispatch=self._dispatch, finish=self._finish,
            max_batch=max_batch, max_wait_s=self._max_wait,
            name="sonata_stream_decoder", keyed=True,
            alive=lambda: self._voice_ref() is not None,
            closed_reason=self._reason, poll_s=5.0)
        self.stats = self._core.stats

    # thread handles pinned by the close/teardown tests
    @property
    def _worker(self):
        return self._core._worker

    @property
    def _finisher(self):
        return self._core._finisher

    def close(self) -> None:
        """Stop both threads and fail any work still queued.

        The core joins the worker before draining so nothing is added to
        a queue after its drain; requests already dispatched to the
        device resolve normally via the finisher before it exits."""
        self._core.shutdown(join_timeout_s=10.0)

    def submit(self, z_row, start: int, width: int, sid: "Optional[int]",
               stream=None, epilogue=None):
        """Enqueue a window decode; returns a Future of the [width*hop]
        waveform — or, with ``epilogue=(lo, hi)`` (the fused-epilogue
        arm), of an ``(i16 samples, peak)`` pair already tapered on
        device.  ``z_row``: [F, C] device array.  ``stream`` is the
        iteration-mode join handle — ignored here (dispatch mode has no
        resident-stream state).

        The window is sliced out of ``z_row`` here, eagerly (a tiny
        on-device op), so everything behind the queue handles fixed
        [width, C] windows regardless of the utterance's frame bucket —
        see :meth:`PiperVoice._decode_windows_batch_fn`.  Fused and
        plain submissions carry distinct keys (different executables
        AND result types), so they never share a dispatch group."""
        window = jax.lax.dynamic_slice_in_dim(
            z_row, jnp.int32(start), width, axis=0)
        fused = epilogue is not None
        payload = ((window, sid, epilogue[0], epilogue[1]) if fused
                   else (window, sid))
        item = WorkItem(payload, key=(width, sid is not None, fused))
        if self._core.closed:
            try_set_exception(item.future, OperationError(self._reason))
            return item.future
        self._core.put(item)
        return item.future

    def decode(self, z_row, start: int, width: int,
               sid: "Optional[int]") -> np.ndarray:
        """Blocking variant of :meth:`submit`."""
        return self.submit(z_row, start, width, sid).result()

    def _dispatch(self, group: list):
        v = self._voice_ref()
        if v is None:
            raise OperationError("voice was garbage-collected")
        n = len(group)
        # any multi-window group pads to ONE canonical batch size: the
        # executable set is then exactly {b=1, b=max} — both prewarmed
        # — so concurrency can never hit a cold compile mid-request.
        # The padding rows' decode compute is cheap next to the
        # XLA-compile stall a graduated bucket ladder risks per rung.
        # (Iteration mode walks the graduated ladder instead — and warms
        # every rung through the lattice; see _IterationStreamDecoder.)
        b = self._max_batch if n > 1 else 1
        fused = group[0].key[2]
        fn, args = _assemble_window_dispatch(
            v, group[0].key, [item.payload for item in group], b)
        out = fn(*args)  # async dispatch
        prefetch_to_host(out)
        self._core.bump("requests", n)
        self._core.bump("dispatches")
        # padding accounting, same keys as the iteration loop's stats —
        # the bench's iteration-vs-dispatch A/B compares these directly
        self._core.bump("rows", n)
        self._core.bump("padded_rows", b - n)
        return (out, fused)

    def _finish(self, group: list, ticket) -> None:
        out, fused = ticket
        results = _fetch_window_results(out, len(group), fused)
        for item, res in zip(group, results):
            try_set_result(item.future, res)


class _IterationStreamDecoder:
    """Iteration-mode window decoder (``SONATA_BATCH_MODE=iteration``).

    Same ``submit`` surface as :class:`_StreamDecodeCoalescer`, but the
    engine underneath is the persistent
    :class:`~sonata_tpu.synth.batching.IterationLoop`: a stream *joins*
    the device's running batch once its encode lands, each of its window
    decodes rides an iteration alongside every other resident stream's
    rows, and the stream *retires* at an iteration boundary when it ends.
    No wave-gather wait window, and the batch axis steps the graduated
    bucket ladder (1, 2, 4, 8) — lattice-warmed, so occupancy-sized
    dispatches stay recompile-free where dispatch mode overpads every
    multi-stream wave to the canonical max.
    """

    def __init__(self, voice: "PiperVoice", *, max_batch: int = 8):
        import weakref

        self._voice_ref = weakref.ref(voice)
        self._max_batch = max_batch
        self._max_wait = 0.0  # no gather window: joins happen at
        # iteration boundaries, not inside a wait loop
        attrs = {}
        device = getattr(voice, "device", None)
        if device is not None:
            attrs["device"] = str(device)
        # two-phase: _dispatch enqueues the device program (async D2H
        # prefetch started), _finish blocks on the result — with
        # SONATA_ITER_PIPELINE (default on) the loop's finisher thread
        # fetches iteration k while the worker dispatches k+1
        self._loop = IterationLoop(self._dispatch, max_batch=max_batch,
                                   name="sonata_iter_decode", attrs=attrs,
                                   finish=self._finish)
        self.stats = self._loop.stats

    # -- stream lifecycle (stream_synthesis drives this) -----------------
    def join(self, deadline=None):
        return self._loop.join(deadline)

    def retire(self, handle) -> None:
        self._loop.retire(handle)

    def start_draining(self) -> None:
        self._loop.start_draining()

    @property
    def resident_streams(self) -> int:
        return self._loop.resident_streams

    def submit(self, z_row, start: int, width: int, sid: "Optional[int]",
               stream=None, epilogue=None):
        """Same eager-slice contract as the dispatch-mode coalescer
        (incl. the fused-epilogue ``epilogue=(lo, hi)`` arm).  Without a
        ``stream`` handle (direct callers, tools) the row rides as a
        one-iteration stream that retires when its future resolves."""
        window = jax.lax.dynamic_slice_in_dim(
            z_row, jnp.int32(start), width, axis=0)
        fused = epilogue is not None
        payload = ((window, sid, epilogue[0], epilogue[1]) if fused
                   else (window, sid))
        key = (width, sid is not None, fused)
        if stream is not None:
            return self._loop.submit(stream, key, payload)
        try:
            handle = self._loop.join()
        except OperationError as e:
            # closed/draining: fail the future instead of raising — the
            # same fail-fast contract as the dispatch-mode coalescer
            from concurrent.futures import Future

            fut: Future = Future()
            fut.set_exception(e)
            return fut
        fut = self._loop.submit(handle, key, payload)
        fut.add_done_callback(lambda _f: self._loop.retire(handle))
        return fut

    def decode(self, z_row, start: int, width: int,
               sid: "Optional[int]") -> np.ndarray:
        """Blocking variant of :meth:`submit`."""
        return self.submit(z_row, start, width, sid).result()

    def close(self) -> None:
        self._loop.close()

    # -- one iteration's device call (two-phase) ---------------------------
    def _dispatch(self, key, payloads, b: int):
        """DISPATCH phase: enqueue the iteration's device program and
        start the async D2H copy, without blocking on the result — the
        loop's finisher (``_finish``) fetches while the next iteration
        dispatches (``SONATA_ITER_PIPELINE``)."""
        v = self._voice_ref()
        if v is None:
            raise OperationError("voice was garbage-collected")
        width, has_sid, fused = key
        n = len(payloads)
        cache_key = v._wdec_cache_key(width, b, has_sid, fused)
        with v._jit_lock:
            cached = cache_key in v._dec_cache
        fn, args = _assemble_window_dispatch(v, key, payloads, b)
        out = fn(*args)  # async dispatch
        prefetch_to_host(out)
        attrs = {"frame_bucket": width, "text_bucket": 0,
                 "compile": "cached" if cached else "cold"}
        voice_label = getattr(v, "scope_voice", None)
        if voice_label is not None:
            attrs["voice"] = voice_label
        return (out, n, fused), attrs

    @staticmethod
    def _finish(ticket):
        """FINISH phase: the blocking fetch — the only host sync on the
        iteration path, and it runs on the finisher thread so iteration
        k+1's dispatch overlaps it."""
        out, n, fused = ticket
        return _fetch_window_results(out, n, fused)


class _StreamStageCoalescer:
    """Shared dispatcher for streaming encode+acoustics stages.

    The window-decode coalescer (above) removed the per-chunk serialization
    across concurrent streams, but every stream still paid its own serial
    encode and acoustics dispatches at start — at 8 concurrent streams
    those per-stream stages dominated TTFB.  Here stream *starts* that
    arrive within ``max_wait_ms`` and share a text bucket become one
    batched encode and one batched acoustics dispatch; per-row synthesis
    scales and speaker ids ride the same row-wise arrays the batch path
    uses, so streams with different configs still share a dispatch.

    Pipeline shape mirrors the decode coalescer (and lives in the same
    :class:`~sonata_tpu.synth.batching.BatchingCore`): a dispatcher
    thread groups and enqueues device programs; a finisher thread blocks
    on each group's (async-prefetched) frame counts, handles the rare
    frame-budget retry, and resolves per-stream futures with their z row.
    """

    def __init__(self, voice: "PiperVoice", *, max_batch: int = 8,
                 max_wait_ms: float = 8.0):
        # max_wait is 4x the decode coalescer's: the stage runs once per
        # stream (vs once per chunk), so a slightly longer gather window
        # costs little TTFB but catches burst arrivals that thread
        # scheduling spreads over a few milliseconds
        import weakref

        self._voice_ref = weakref.ref(voice)
        self._max_batch = max_batch
        self._max_wait = max_wait_ms / 1000.0
        self._reason = "stream-stage coalescer closed (voice unloaded)"
        self._core = BatchingCore(
            dispatch=self._dispatch, finish=self._finish,
            max_batch=max_batch, max_wait_s=self._max_wait,
            name="sonata_stream_stages", keyed=True,
            alive=lambda: self._voice_ref() is not None,
            closed_reason=self._reason, poll_s=5.0)
        self.stats = self._core.stats

    @property
    def _worker(self):
        return self._core._worker

    @property
    def _finisher(self):
        return self._core._finisher

    def close(self) -> None:
        """Stop both threads and fail any work still queued (see
        :meth:`_StreamDecodeCoalescer.close`)."""
        self._core.shutdown(join_timeout_s=10.0)

    def start(self, ids: list, sc):
        """Blocking: run encode+acoustics for one stream (possibly batched
        with others).  Returns ``(z_row, total_frames, f, sid0)`` where
        ``z_row`` is the [f, C] on-device latent, ``total_frames`` the true
        frame count, ``f`` the allocated frame bucket, and ``sid0`` the
        row's speaker id (None on single-speaker voices)."""
        if self._core.closed:
            raise OperationError(self._reason)
        item = WorkItem((ids, sc),
                        key=(bucket_for(len(ids), TEXT_BUCKETS),))
        self._core.put(item)
        return item.future.result()

    def _dispatch(self, group: list):
        v = self._voice_ref()
        if v is None:
            raise OperationError("voice was garbage-collected")
        ids_list = [item.payload[0] for item in group]
        scs = [item.payload[1] for item in group]
        # same canonical-batch rule as the decode coalescer: any
        # multi-stream group pads to max_batch rows, so only the
        # (b=1, b=max) encode/acoustics shapes exist and prewarm
        # covers them completely
        if len(group) > 1:
            pad_rows = self._max_batch - len(group)
            ids_list = ids_list + [[0]] * pad_rows
            scs = scs + [scs[0]] * pad_rows
        ids, lens, b, t = v._pad_batch(ids_list)
        speakers = None
        if v.multi_speaker:
            speakers = [sc.speaker[1] if sc.speaker else 0 for sc in scs]
        sid = v._sid_array(scs[0], b, speakers)
        nw, ls, ns, ls_host = v._scale_arrays(scs[0], b, scales=scs)
        weighted = max(len(row) * max(ls_host[i], 0.05)
                       for i, row in enumerate(ids_list))
        f = v.frame_estimator.bucket(weighted)
        # one split key per dispatch, like the fused batch path — a
        # frame-budget retry reuses it for identical audio
        rng_enc, rng_aco = jax.random.split(v._next_rng())
        enc_args = [v.params, ids, lens, rng_enc, nw, ls]
        if sid is not None:
            enc_args.append(sid)
        m_p, logs_p, w_ceil, x_mask = v._encode_fn(b, t)(*enc_args)
        # per-row frame counts: prefetched so the finisher's fetch
        # rides behind the acoustics dispatch
        frames_vec = jnp.sum(w_ceil.reshape(b, -1), axis=1)
        frames_vec.copy_to_host_async()

        def run_acoustics(bucket: int):
            args = [v.params, m_p, logs_p, w_ceil, x_mask, rng_aco, ns]
            if sid is not None:
                args.append(sid)
            return v._acoustics_fn(b, t, bucket)(*args)

        z, _y_lengths = run_acoustics(f)
        self._core.bump("requests", len(group))
        self._core.bump("dispatches")
        self._core.bump("rows", len(group))
        self._core.bump("padded_rows", b - len(group))
        return (z, frames_vec, f, weighted, speakers, run_acoustics)

    def _finish(self, group: list, ticket) -> None:
        z, frames_vec, f, weighted, speakers, run_acoustics = ticket
        v = self._voice_ref()
        frames = np.asarray(jax.device_get(frames_vec)).astype(int)
        actual = int(frames[:len(group)].max())
        if v is not None:
            v.frame_estimator.observe(weighted, actual)
        if actual > f and v is not None:  # clipped: redo, same rng
            f = bucket_for(actual, FRAME_BUCKETS)
            z, _ = run_acoustics(f)
        for i, item in enumerate(group):
            sid0 = speakers[i] if speakers is not None else None
            try_set_result(item.future, (z[i], int(frames[i]), f, sid0))
