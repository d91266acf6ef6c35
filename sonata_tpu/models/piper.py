"""PiperVoice: the concrete TTS model behind the ``Model`` protocol.

TPU-native analogue of the reference's ``sonata-piper`` crate
(``crates/sonata/models/piper/src/lib.rs``), replacing its two ORT sessions
with staged jitted XLA executables:

- reference ``VitsModel::infer_with_values`` (``:342-399``, one ONNX run)
  → two dispatches here: ``encode`` (text bucket) + ``synthesize`` (frame
  bucket).  The split exists because ONNX hides a data-dependent shape —
  the frame count — that XLA must see as static; bucketing bounds compiles.
- reference ``VitsStreamingModel`` (``:480-669``) → the same ``encode``
  plus ``acoustics``, then per-chunk jitted decodes following the
  ``AdaptiveMelChunker`` schedule (:mod:`.chunker`).
- reference ``speak_batch`` loops sentences through single inference
  (``:425-437``); here it is a true padded batch (the designed
  improvement, SURVEY §2.4): one front program for the batch, then one
  back program per group of rows of like length, planned from the frame
  counts the front reports (:mod:`.shape_plan`).

Thread-safety: the synthesis config sits behind a lock (reference uses an
``RwLock``, ``:215-231``); jit caches are lock-protected; phonemization is
serialized inside the text backend.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Iterator, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..audio import Audio, AudioSamples
from ..core import (
    AudioInfo,
    BaseModel,
    FailedToLoadResource,
    OperationError,
    Phonemes,
)
from ..serving import tracing
from ..synth.batching import effective_batch_mode, resolve_batch_mode
from ..text import text_to_phonemes
from ..text.tashkeel import TashkeelEngine, get_default_engine
from ..utils.buckets import (
    BATCH_BUCKETS,
    FRAME_BUCKETS,
    TEXT_BUCKETS,
    bucket_for,
    pad_to,
)
from ..utils.dispatch_policy import (
    COALESCING_DEFAULTS,
    DispatchPolicy,
    resolve_policy,
)
from ..utils.transfer import prefetch_to_host
from . import decode_opts, shape_plan, vits
from .chunker import CROSSFADE_SAMPLES, plan_chunks
from .config import ModelConfig, SynthesisConfig, default_phoneme_id_map
from .serialization import load_params


class PiperVoice(BaseModel):
    """A loaded Piper voice: config + params + compiled-executable caches."""

    def __init__(self, config: ModelConfig, params, *, seed: int = 0,
                 tashkeel: Optional[TashkeelEngine] = None, mesh=None,
                 compute_dtype: Optional[str] = None,
                 dispatch_policy: "Optional[DispatchPolicy]" = None,
                 fused_epilogue: Optional[str] = None,
                 decode_quant: Optional[str] = None):
        self.config = config
        self.hp = config.hyper
        # int8 weight-only decoder arm (SONATA_DECODE_QUANT=int8):
        # per-channel symmetric quantization of the HiFi-GAN conv
        # weights at load, dequantized inside the jitted decode
        # (vits.decode_with) — activations stay f32/bf16.  Parity-gated
        # by the spectral-distance test in tests/test_decode_opts.py.
        self.decode_quant = decode_opts.resolve_decode_quant(decode_quant)
        if self.decode_quant == "int8":
            if mesh is not None:
                raise OperationError(
                    "SONATA_DECODE_QUANT=int8 does not compose with a "
                    "device mesh (param shardings assume f32 leaves)")
            if not decode_opts.decoder_is_quantized(params["dec"]):
                params = dict(params)
                params["dec"] = decode_opts.quantize_decoder(
                    params["dec"])
        # fused decode epilogue (SONATA_FUSED_EPILOGUE=lax|off,
        # default lax): streaming window decode + crossfade taper +
        # peak-scaled i16 quantize run as ONE device program per
        # (width, batch rung) — see _decode_windows_fused_fn.
        self.fused_epilogue = decode_opts.resolve_fused_epilogue(
            fused_epilogue)
        self.mesh = mesh  # jax.sharding.Mesh → batch rides the data axis
        # the weights live on the device from here on, under the placement
        # the programs expect (a mesh: the per-leaf shardings _jit
        # declares), so no program call hands them over again; leaves
        # that are placed already (init_vits, a replica's) stay where
        # they are, and the host copy goes with the caller's reference
        if mesh is None:
            self.params = jax.device_put(params)
        else:
            from ..parallel.mesh import param_shardings

            self.params = jax.device_put(
                params, param_shardings(mesh, params))
        #: bytes of the weight tree a program call would still take from
        #: the host (read once, here: a dispatch record adds it to its
        #: ``upload_bytes`` without walking the tree)
        self._weights_host_bytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.params)
            if not isinstance(leaf, jax.Array))
        # Reduced-precision policy for the HiFi-GAN conv stack (the FLOPs):
        # "bfloat16" keeps the MXU in its native single-pass mode.  Audio
        # leaves the graph float32 either way (vits.decode_with casts back
        # before the final tanh); measured ~38 dB SNR vs float32 — below
        # the i16 output floor, so default stays float32 and serving can
        # opt in per deployment (SONATA_COMPUTE_DTYPE=bfloat16).
        import os

        compute_dtype = compute_dtype or os.environ.get(
            "SONATA_COMPUTE_DTYPE")
        if compute_dtype in (None, "", "float32", "f32"):
            self.compute_dtype = None
        elif compute_dtype in ("bfloat16", "bf16"):
            self.compute_dtype = jnp.bfloat16
        else:
            raise OperationError(
                f"unsupported compute_dtype {compute_dtype!r} "
                "(use float32 or bfloat16)")
        self.multi_speaker = config.num_speakers > 1
        self._synth_lock = threading.RLock()
        self._synth_config = config.inference.copy()
        self._jit_lock = threading.Lock()
        self._enc_cache: dict = {}
        self._aco_cache: dict = {}
        self._dec_cache: dict = {}
        # the stock path's two kinds of program: the front at (b, t), the
        # back at (b, t, f); the back shapes whose compile has finished;
        # and per program an event, set once its first call has returned
        self._front_cache: dict = {}
        self._back_cache: dict = {}
        self._back_warm: set = set()
        self._first_calls: dict = {}
        self._gather_rows = self._gather_fn()
        self._row_iota: dict = {}
        # the stream engines (synth/stream_engines.py), built on first use
        self._stream_coalescer = None
        self._stage_coalescer = None
        #: iteration-mode engine (SONATA_BATCH_MODE=iteration): the
        #: persistent per-device decode loop; coexists with the
        #: dispatch-mode coalescer so the degradation ladder can force
        #: new streams back to dispatch mode while resident ones finish
        self._iter_decoder = None
        #: voice id the serving runtime registered this model under —
        #: stamps the iteration loop's per-iteration scope attribution
        #: (the scheduler path carries it via trace_attrs instead)
        self.scope_voice: Optional[str] = None
        # backend-adaptive dispatch policy (utils/dispatch_policy): pass
        # one explicitly to pin the serving shape; None resolves lazily
        # on first use (env overrides → backend fast path → cached probe)
        # so plain construction never pays a probe dispatch.
        self._dispatch_policy = dispatch_policy
        self._policy_lock = threading.Lock()
        self._voice_closed = False
        # encodability diagnostics: symbols the voice's phoneme_id_map
        # could not encode (dropped, reference-identically, at encode
        # time — piper/src/lib.rs:243).  A nonzero rate means the G2P
        # front-end and the voice's symbol table disagree; for tonal
        # languages that can silently delete the whole tone system.
        self.drop_stats = {"symbols_total": 0, "symbols_dropped": 0,
                           "dropped": {}}
        self._warned_drops: set = set()
        #: frames per id as observed, for the shapes named before a row's
        #: durations exist: the warm-up lattice, the stream stages
        #: (shape_plan owns the arithmetic)
        self.frame_estimator = shape_plan.FrameEstimator()
        self._rng_lock = threading.Lock()
        self._rng_counter = 0
        self._seed = seed
        # Arabic voices get the diacritizer automatically
        # (parity: piper/src/lib.rs:63-77)
        self._tashkeel = tashkeel
        if self._tashkeel is None and config.espeak_voice.startswith("ar"):
            self._tashkeel = get_default_engine()

    # ------------------------------------------------------------------
    # factories
    # ------------------------------------------------------------------

    @classmethod
    def from_config_path(cls, config_path: Union[str, Path],
                         **kwargs) -> "PiperVoice":
        """Load a voice from a Piper ``*.json`` config.

        Weight resolution (reference loads ``config path minus .json`` as
        ONNX, ``piper/src/lib.rs:98-108``): tries, in order, the sidecar
        ``<stem>.npz`` (native), ``<stem>.onnx`` (imported), ``<stem>.pt`` /
        ``.ckpt`` (torch checkpoint import).
        """
        config = ModelConfig.from_path(config_path)
        stem = Path(config_path)
        stem = stem.with_suffix("") if stem.suffix == ".json" else stem
        n_vocab = max(config.num_symbols,
                      1 + max((max(v) for v in config.phoneme_id_map.values()),
                              default=0))
        # Piper convention: "voice.onnx" + "voice.onnx.json", so the config
        # path minus ".json" may itself be the ONNX file (piper/lib.rs:98-108)
        onnx_path = stem if stem.suffix == ".onnx" else stem.with_suffix(".onnx")
        # streaming ("rt") voice directories split the exported graph into
        # encoder.onnx + decoder.onnx siblings of the config
        # (piper/src/lib.rs:90-96).  The two initializer sets partition the
        # same VITS weights; merged, they feed the one staged model — the
        # split *runtime* is superfluous here because the serving path is
        # already staged into encode/acoustics/decode executables.
        enc_path = Path(config_path).with_name("encoder.onnx")
        dec_path = Path(config_path).with_name("decoder.onnx")
        if stem.with_suffix(".npz").exists():  # native format stays first
            params = load_params(stem.with_suffix(".npz"))
        elif config.streaming and enc_path.exists() and dec_path.exists():
            try:
                from .import_onnx import import_onnx_weights
            except ImportError as e:
                raise FailedToLoadResource(
                    f"ONNX weight import unavailable: {e}") from e
            params = import_onnx_weights(
                (enc_path, dec_path), config.hyper, n_vocab=n_vocab,
                n_speakers=config.num_speakers)
        elif onnx_path.exists():
            try:
                from .import_onnx import import_onnx_weights
            except ImportError as e:
                raise FailedToLoadResource(
                    f"ONNX weight import unavailable: {e}") from e
            params = import_onnx_weights(
                onnx_path, config.hyper, n_vocab=n_vocab,
                n_speakers=config.num_speakers)
        elif any(stem.with_suffix(s).exists() for s in (".pt", ".ckpt", ".pth")):
            try:
                from .import_torch import import_torch_checkpoint
            except ImportError as e:
                raise FailedToLoadResource(
                    f"torch checkpoint import unavailable: {e}") from e
            ckpt = next(stem.with_suffix(s) for s in (".pt", ".ckpt", ".pth")
                        if stem.with_suffix(s).exists())
            params = import_torch_checkpoint(
                ckpt, config.hyper, n_vocab=n_vocab,
                n_speakers=config.num_speakers)
        else:
            raise FailedToLoadResource(
                f"no weights found next to {config_path} "
                f"(looked for {stem}.npz/.onnx/.pt/.ckpt)")
        return cls(config, params, **kwargs)

    @classmethod
    def random(cls, config: Optional[ModelConfig] = None, *, seed: int = 0,
               compute_dtype: Optional[str] = None,
               **config_overrides) -> "PiperVoice":
        """A randomly-initialized voice (tests, benchmarks, dry runs)."""
        if config is None:
            d = {
                "audio": {"sample_rate": 22050, "quality": "medium"},
                "num_speakers": 1,
                "espeak": {"voice": "en-us"},
                "phoneme_id_map": default_phoneme_id_map(),
            }
            d.update(config_overrides)
            d["num_symbols"] = len(d["phoneme_id_map"])
            config = ModelConfig.from_dict(d)
        n_vocab = config.num_symbols
        params = vits.init_vits(jax.random.PRNGKey(seed), config.hyper,
                                n_vocab=n_vocab,
                                n_speakers=config.num_speakers)
        return cls(config, params, seed=seed, compute_dtype=compute_dtype)

    def replica_for_device(self, device, *,
                           seed_offset: int = 0) -> "PiperVoice":
        """A copy of this voice pinned to one device (replica-pool serving).

        ``jax.device_put`` commits the params to ``device`` (a copy from
        the chip this voice's own tree lives on, or the same buffers
        where that chip is ``device``); every jitted dispatch then runs
        there (a committed operand places the whole computation), so N
        replicas built from one loaded voice
        occupy N chips with independent executables, RNG streams
        (``seed_offset`` keeps replica draws distinct), and jit caches —
        the isolation the pool's circuit breaker relies on.  Mutually
        exclusive with a mesh: a mesh makes all chips one SPMD dispatch,
        a pool makes each chip its own failure domain.
        """
        if self.mesh is not None:
            raise OperationError(
                "replica pools and device meshes are mutually exclusive "
                "(a mesh already spans the local chips as one dispatch)")
        params = jax.device_put(self.params, device)
        replica = PiperVoice(
            self.config, params, seed=self._seed + seed_offset,
            tashkeel=self._tashkeel,
            compute_dtype=("bfloat16" if self.compute_dtype is not None
                           else None),
            dispatch_policy=self._dispatch_policy,
            fused_epilogue=self.fused_epilogue,
            decode_quant=self.decode_quant or "off")
        replica.device = device
        replica.scope_voice = self.scope_voice
        return replica

    # ------------------------------------------------------------------
    # Model protocol
    # ------------------------------------------------------------------

    def audio_output_info(self) -> AudioInfo:
        return AudioInfo(sample_rate=self.config.sample_rate)

    def get_language(self) -> Optional[str]:
        return self.config.language or self.config.espeak_voice

    def get_speakers(self) -> Optional[dict[int, str]]:
        if not self.multi_speaker:
            return None
        return self.config.reversed_speaker_map()

    def properties(self) -> dict[str, str]:
        return {"quality": self.config.quality or "unknown"}

    def supports_streaming_output(self) -> bool:
        return True

    def get_default_synthesis_config(self) -> SynthesisConfig:
        return self.config.inference.copy()

    def get_fallback_synthesis_config(self) -> SynthesisConfig:
        with self._synth_lock:
            return self._synth_config.copy()

    def set_fallback_synthesis_config(self, config: Any) -> None:
        if not isinstance(config, SynthesisConfig):
            raise OperationError(
                "invalid synthesis config type "
                f"{type(config).__name__}")  # parity: Any-downcast failure
        with self._synth_lock:
            self._synth_config = config.copy()

    def phonemize_text(self, text: str) -> Phonemes:
        # Arabic: diacritize first (piper/src/lib.rs:253-258,270-281).
        # Digits expand to MSA number words BEFORE diacritization so the
        # inserted words receive harakat like any other Arabic word —
        # expanding after (in the normalizer) would feed the letter map
        # vowel-less consonant skeletons.
        if self._tashkeel is not None:
            with tracing.span("text-normalize", stage="tashkeel"):
                from ..text.rule_g2p import (
                    arabic_number_to_words, expand_numbers)

                text = expand_numbers(text, arabic_number_to_words)
                text = self._tashkeel.diacritize(text)
        return text_to_phonemes(
            text, voice=self.config.espeak_voice,
            remove_lang_switch_flags=True,
        )

    def _encode_phonemes(self, phonemes: str) -> list[int]:
        """Encode one sentence, feeding the voice's drop-rate diagnostics.

        Encoding behavior is reference-identical (unknown symbols dropped,
        piper/src/lib.rs:243); this wrapper only *counts* the drops and
        warns once per distinct symbol so a G2P/symbol-table mismatch is
        visible instead of silently degrading audio."""
        ids, dropped = self.config.phonemes_to_ids_diag(phonemes)
        stats = self.drop_stats
        stats["symbols_total"] += len(phonemes)
        if dropped:
            stats["symbols_dropped"] += len(dropped)
            for ch in dropped:
                stats["dropped"][ch] = stats["dropped"].get(ch, 0) + 1
                if ch not in self._warned_drops and not ch.isspace():
                    self._warned_drops.add(ch)
                    import logging

                    logging.getLogger("sonata").warning(
                        "phoneme %r (U+%04X) is not in this voice's "
                        "phoneme_id_map and was dropped at encoding",
                        ch, ord(ch))
        return ids

    def speak_one_sentence(self, phonemes: str) -> Audio:
        return self.speak_batch([phonemes])[0]

    # Representative prewarm texts: short / medium / long sentences cover
    # the common text buckets (and, batched together, the common group
    # shapes).
    _PREWARM_TEXTS = [
        "Hello there.",
        "This server compiles its executables before the first request.",
        "A longer sentence exercises the larger text and frame buckets so "
        "that real traffic arriving right after startup never waits on a "
        "fresh compilation of the synthesis pipeline.",
    ]

    def prewarm(self, texts: Optional[list[str]] = None, *,
                streaming: bool = False, chunk_size: int = 55,
                chunk_padding: int = 3) -> int:
        """Compile the common executables before serving traffic.

        A cold voice pays XLA compilation on the first request that hits
        each (batch, text, frame) bucket; the reference has no equivalent
        because ONNX
        sessions are shape-polymorphic.  Synthesizes a representative
        batch until the executable cache stops growing, then compiles the
        neighbor frame buckets (a request's frames ride its random
        duration draw, so traffic lands one bucket over routinely).
        With ``streaming=True`` also drains one realtime stream, warming
        the encoder/acoustics stages and the window decoders for the
        given chunk schedule.  Returns the number of compiled
        full-pipeline shapes.  Persistent caching pairs well with this
        (``jax_compilation_cache_dir``): after the first boot, prewarm
        mostly re-loads executables from disk.
        """
        phonemes = [p for t in (texts or self._PREWARM_TEXTS)
                    for p in self.phonemize_text(t)]
        if not phonemes:  # e.g. caller texts of pure punctuation
            return len(self._back_cache)
        for _ in range(4):
            n_compiled = len(self._back_cache)
            self.speak_batch(phonemes)
            if len(self._back_cache) == n_compiled:
                break
        self.prewarm_neighbor_buckets()
        if streaming:
            # one streamed drain per distinct text bucket: streaming
            # coverage must match the batch path's, or the first real
            # stream in an undrained bucket pays the cold encode mid-TTFB
            by_bucket: dict[int, str] = {}
            for p in phonemes:
                tb = bucket_for(len(self.config.phonemes_to_ids(p)),
                                TEXT_BUCKETS)
                if tb not in by_bucket or len(p) > len(by_bucket[tb]):
                    by_bucket[tb] = p
            for p in by_bucket.values():
                for _chunk in self.stream_synthesis(p, chunk_size,
                                                    chunk_padding):
                    pass
            self._prewarm_stream_batches()
        return len(self._back_cache)

    def _prewarm_stream_batches(self) -> None:
        """Compile the coalesced-batch window decoders for every streamed
        width warmed so far.

        Under concurrent load the stream coalescers pad every multi-request
        group to ONE canonical batch size — the executable set is exactly
        {b=1, b=max} per stage, never a graduated bucket ladder — so a
        sequential warmup (which only compiles b=1) leaves precisely one
        more shape per stage to warm here; without it the first wave of
        real concurrency pays one mid-request XLA compile per stage.
        Runs each shape once with dummy windows, blocking, so the
        executables are resident (and in the persistent cache) before
        traffic arrives.  Best-effort: a failing warm thunk (e.g. a
        sharding mismatch on an exotic mesh) must not abort serving.
        """
        with self._jit_lock:
            seen = [k for k in self._dec_cache if isinstance(k, tuple)
                    and k and k[0] in ("wbatch", "wfused")]
            enc_seen = [k for k in self._enc_cache]
            aco_seen = list(self._aco_cache)
        co = self._stream_decoder
        c = self.hp.inter_channels
        hop = self.hp.hop_length
        thunks = []
        # every width must be warm at BOTH canonical batch sizes: the
        # sequential drain itself coalesces its look-ahead windows, so a
        # width can enter the cache at b=max only — and the first lone
        # straggler at that width would then pay a b=1 cold compile
        # mid-request (the exact stall prewarm exists to prevent).
        # Iteration mode pads to the graduated ladder instead of the
        # canonical pair, so every rung up to max_batch warms.
        batch_set = shape_plan.window_decoder_batches(
            "iteration" if co is self._iter_decoder else "dispatch",
            co._max_batch)
        # each variant (fused vs plain) warms wherever it was seen — a
        # fused-default voice drains streams through wfused shapes while
        # direct decode() callers may still touch wbatch ones
        widths = {(k[1], k[3], k[0] == "wfused") for k in seen}
        for (width, has_sid, fused) in widths:
            for b in batch_set:

                def warm_dec(width=width, b=b, has_sid=has_sid,
                             fused=fused):
                    args = [self.params, jnp.zeros((b, width, c),
                                                   jnp.float32)]
                    if fused:
                        fn = self._decode_windows_fused_fn(width, b,
                                                           has_sid)
                        args += [jnp.zeros((b,), jnp.int32),
                                 jnp.full((b,), width * hop, jnp.int32)]
                    else:
                        fn = self._decode_windows_batch_fn(width, b,
                                                           has_sid)
                    if has_sid:
                        args.append(jnp.zeros((b,), jnp.int32))
                    jax.block_until_ready(fn(*args))

                thunks.append(warm_dec)
        # the stage coalescer batches stream STARTS too: warm the b=max
        # encode/acoustics shapes it dispatches under concurrency.  Its
        # dispatch routes through _pad_batch, which can round the batch up
        # past max_batch to a multiple of the mesh data axis — derive the
        # warm batch through the same call or the warmed shape would never
        # match dispatch-time shapes on a non-dividing mesh.
        _, _, stage_b, _ = self._pad_batch(
            [[0]] * self._stream_stages._max_batch)
        # acoustics frame buckets ride the adaptive estimator, which keeps
        # refining between warm and real traffic — warm each seen bucket's
        # neighbors too, like prewarm_neighbor_buckets does for the fused
        # path, or the first post-warm stream lands one bucket over cold
        aco_targets = set(aco_seen)
        for fa in aco_seen:
            aco_targets |= shape_plan.neighbor_frame_buckets(fa)
        for (eb, t) in enc_seen:
            # warm both the shape already seen (b=1 drains) and the
            # canonical coalesced-batch shape
            for b in {eb, stage_b}:

                def warm_stage(t=t, b=b):
                    ids = jnp.zeros((b, t), jnp.int32)
                    lens = jnp.ones((b,), jnp.int32)
                    nw = jnp.full((b,), 0.8, jnp.float32)
                    ls = jnp.ones((b,), jnp.float32)
                    ns = jnp.full((b,), 0.667, jnp.float32)
                    rng = jax.random.PRNGKey(0)
                    enc_args = [self.params, ids, lens, rng, nw, ls]
                    if self.multi_speaker:
                        enc_args.append(jnp.zeros((b,), jnp.int32))
                    out = self._encode_fn(b, t)(*enc_args)
                    m_p, logs_p, w_ceil, x_mask = jax.block_until_ready(out)
                    for fa in sorted(aco_targets):
                        aco_args = [self.params, m_p, logs_p, w_ceil,
                                    x_mask, rng, ns]
                        if self.multi_speaker:
                            aco_args.append(jnp.zeros((b,), jnp.int32))
                        jax.block_until_ready(
                            self._acoustics_fn(b, t, fa)(*aco_args))

                thunks.append(warm_stage)
        def best_effort(th):
            try:
                th()
            except Exception as e:  # warm failure must not abort serving
                import logging

                logging.getLogger("sonata").warning(
                    "prewarm thunk failed (continuing): %s", e)

        # compile concurrently: each thunk's first call blocks in XLA, and
        # the compiles for distinct shapes don't depend on each other —
        # 4 workers roughly quarter a cold boot's multi-minute warm
        with ThreadPoolExecutor(4, thread_name_prefix="sonata_warm") as ex:
            for res in ex.map(best_effort, thunks):
                pass

    def prewarm_neighbor_buckets(self) -> None:
        """Compile the frame buckets adjacent to every cached back
        program's (one blocking :meth:`warm_shape` each — the single
        place the dummy-argument signature lives)."""
        for (b, t, f) in list(self._back_cache):
            for nf in shape_plan.neighbor_frame_buckets(f):
                self.warm_shape((b, t, nf))

    # ------------------------------------------------------------------
    # bucket-lattice AOT warmup (serving/warmup.py drives this contract)
    # ------------------------------------------------------------------

    def lattice_shapes(self, mode: str = "full") -> list[tuple]:
        """The shapes a restart must warm, smallest first: the
        ``(batch, text, frame)`` triples real traffic can hit and, in
        iteration mode, the ``("wdec", width, batch, has_sid)`` window
        decoders (:func:`.shape_plan.lattice_shapes` has the rule).
        :meth:`warm_shape` takes either kind."""
        if mode == "off":
            return []  # and no policy probe either
        return shape_plan.lattice_shapes(
            mode, self.frame_estimator,
            float(self.get_fallback_synthesis_config().length_scale),
            multi_speaker=self.multi_speaker, **self._lattice_policy())

    def _lattice_policy(self) -> dict:
        """What the lattice reads of the dispatch policy.  Neither a
        probe failure nor a typo'd ``SONATA_BATCH_MODE`` (which fails
        loudly at stream time) may block boot: the lattice is then the
        batch-1 triples, or the triples alone."""
        try:
            policy = self.dispatch_policy
        except Exception:
            return {"scheduler_max_batch": 1, "stream_decode_max_batch": 1,
                    "batch_mode": None}
        try:
            batch_mode = resolve_batch_mode(policy)
        except OperationError:
            batch_mode = None
        return {"scheduler_max_batch": policy.scheduler_max_batch,
                "stream_decode_max_batch": policy.stream_decode_max_batch,
                "batch_mode": batch_mode}

    def warm_shape(self, shape: tuple[int, int, int]) -> None:
        """Make one (b, t, f) shape hot before traffic: the front program
        at (b, t) and the back program at (b, t, f).

        The front is small and compiles through a dummy-argument jit call
        (riding JAX's persistent compile cache).  The back holds the
        decoder, which is the compile bill, and prefers the **AOT
        executable store**
        (:func:`~sonata_tpu.utils.jax_cache.aot_cache_dir`): a prior
        boot's serialized executable loads in ~0.3 s with zero
        retracing; a cold shape compiles via
        ``jit(...).lower().compile()`` and serializes for the next
        boot.  Either way the executable runs once on what the front made
        of the dummy arguments and is installed into ``_back_cache`` — the
        exact cache real traffic dispatches through (the compiled object is
        callable with the same arguments as the jitted function, and
        takes params as an argument, so one blob serves every voice
        with these dims).
        Falls back to a jit call when AOT is disabled, a mesh is attached,
        or anything in the AOT path fails.  Bypasses :meth:`speak_batch`
        on purpose: dummy zeros must never feed the frame estimator, or
        warmup would corrupt what the lattice was enumerated with.

        Iteration-mode shapes (``("wdec", width, batch, has_sid)`` from
        :meth:`lattice_shapes`) compile the batched window
        decoder directly — a plain jit warm riding the persistent
        compile cache (no AOT store: the decoder program is small and
        retraces in well under a second).
        """
        if shape and shape[0] == "wdec":
            _tag, width, b, has_sid = shape
            # warm the variant real traffic dispatches through: the
            # fused decode+epilogue program when SONATA_FUSED_EPILOGUE
            # is on (the default), the plain window decoder otherwise —
            # warming the wrong one would leave every live iteration
            # cold and trip the PR-9 containment
            fused = self.fused_epilogue != "off"
            args = [self.params,
                    jnp.zeros((b, width, self.hp.inter_channels),
                              jnp.float32)]
            if fused:
                fn = self._decode_windows_fused_fn(width, b, has_sid)
                hop = self.hp.hop_length
                args += [jnp.zeros((b,), jnp.int32),
                         jnp.full((b,), width * hop, jnp.int32)]
            else:
                fn = self._decode_windows_batch_fn(width, b, has_sid)
            if has_sid:
                args.append(jnp.zeros((b,), jnp.int32))
            jax.block_until_ready(fn(*args))
            return
        self._warm_triple(shape)

    def _warm_triple(self, shape: tuple[int, int, int],
                     aot: bool = True) -> None:
        """:meth:`warm_shape` of a (b, t, f) triple.  The first dispatch
        at a (b, t) warms its companions through this with ``aot`` off:
        the blob store is a boot's (a serialized decoder is 100 MB on
        disk), the persistent compile cache serves both."""
        b, t, f = shape
        if (b, t, f) in self._back_warm:
            return  # already hot (traffic or an earlier warm)
        args = self._dummy_back_args(b, t)
        if aot and self.mesh is None:
            from ..utils.jax_cache import aot_cache_dir

            aot_dir = aot_cache_dir()
            if aot_dir is not None:
                try:
                    executable = self._aot_executable(shape, args, aot_dir)
                    # run it once, as the jit warm-up below does: a blob
                    # can load and still not run (XLA:CPU's, when the
                    # executable it was written from came out of the
                    # persistent compile cache), and that has to raise
                    # here, where the jit path can take over, not in
                    # traffic
                    jax.block_until_ready(executable(*args))
                    with self._jit_lock:
                        self._back_cache[(b, t, f)] = executable
                        self._back_warm.add((b, t, f))
                    return
                except Exception as e:
                    import logging

                    logging.getLogger("sonata").warning(
                        "AOT warm of %s failed (%s); falling back to "
                        "jit warmup", shape, e)
        jax.block_until_ready(self._back_fn(b, t, f)(*args))
        with self._jit_lock:
            self._back_warm.add((b, t, f))

    def _dummy_back_args(self, b: int, t: int) -> list:
        """The canonical argument list of a (b, t, *) back program: what
        the front program at (b, t), compiled by this call if need be,
        makes of zero-valued ids — the ONE place the warm/prewarm dummy
        signature lives."""
        rng = jax.random.PRNGKey(0)
        front_args = [self.params,
                      jnp.zeros((b, t), jnp.int32),
                      jnp.ones((b,), jnp.int32),
                      rng,
                      jnp.full((b,), 0.8, jnp.float32),
                      jnp.ones((b,), jnp.float32)]
        sid = [jnp.zeros((b,), jnp.int32)] if self.multi_speaker else []
        *staged, _frames = self._front_fn(b, t)(*front_args, *sid)
        return [self.params, *staged, self._identity_rows(b), rng,
                jnp.full((b,), 0.667, jnp.float32), *sid]

    def _aot_key(self, shape: tuple[int, int, int]) -> str:
        """Cache key for one serialized back program: everything that
        changes the compiled program — jax version, backend, target
        device (a replica's executable is placed on ITS chip), model
        dims, vocab/speaker counts, compute dtype, and the shape.
        Params are an *argument* of the executable, so voices sharing
        dims share blobs."""
        device = getattr(self, "device", None)
        parts = (jax.__version__, jax.default_backend(), str(device),
                 repr(sorted(vars(self.hp).items())),
                 self.config.num_symbols, self.config.num_speakers,
                 str(self.compute_dtype), bool(self.multi_speaker),
                 str(self.decode_quant), "back", tuple(shape))
        return hashlib.blake2b(repr(parts).encode(),
                               digest_size=16).hexdigest()

    def _aot_executable(self, shape: tuple[int, int, int], args: list,
                        aot_dir: str):
        """One shape's AOT back program: loaded from the store, or built
        and serialized into it.  Concurrent writers race safely (atomic
        tmp + rename); a corrupt blob raises and the caller falls back
        to the jit path."""
        import pickle

        from jax.experimental.serialize_executable import (
            deserialize_and_load,
            serialize,
        )

        b, t, f = shape
        path = os.path.join(aot_dir, self._aot_key(shape) + ".aotx")
        if os.path.exists(path):
            with open(path, "rb") as fh:
                payload, in_tree, out_tree = pickle.load(fh)
            # loaded for the device the weights live on: left to its
            # default the loader spreads the executable over every local
            # device, and each call then wants one shard per device
            return deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=list(jax.tree_util.tree_leaves(
                    self.params)[0].devices()))
        executable = self._back_fn(b, t, f).lower(*args).compile()
        tmp = f"{path}.tmp{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "wb") as fh:
            pickle.dump(serialize(executable), fh)
        os.replace(tmp, path)
        return executable

    # Cap on rows per device program: beyond this, padding waste and
    # compile sizes grow without amortizing any more fixed latency.
    MAX_DISPATCH_BATCH = 64
    # Floor on rows of a front batch when a large batch is cut into
    # several for pipelining: below this, a program's fixed cost (launch
    # and result fetch) dominates.
    MIN_DISPATCH_BATCH = 8
    # Decoder programs kept in flight during pipelined batch synthesis.
    PIPELINE_DEPTH = 3
    # Front batches whose program runs ahead of the one being planned
    # (their outputs wait on the device: 2.4 MB at (8, 192)).
    FRONT_LOOKAHEAD = 2

    def speak_batch(self, phoneme_batches: list[str],
                    speakers: Optional[list[Optional[int]]] = None,
                    scales: "Optional[list[Optional[SynthesisConfig]]]"
                    = None) -> list[Audio]:
        """True batched synthesis on the device, in two kinds of program
        with the dispatch plan made between them.

        The *front* program (text encoder and duration predictor) runs
        once per front batch: large corpora are partitioned by text-length
        bucket and chunked to :data:`MAX_DISPATCH_BATCH` rows
        (:func:`.shape_plan.plan_dispatch_groups`).  Its outputs stay on
        the device; only the rows' frame counts come to the host, where
        :func:`.shape_plan.plan_decode_groups` sorts the rows by them and
        cuts them into groups of like length where that saves device
        time.  Each group is one *back* program (acoustics, decoder,
        int16 epilogue) at the frame bucket of its longest row (the
        nearest compiled one above it where its own is cold), so no
        program is ever clipped.  Results reassemble in input order.

        ``speakers``: optional per-sentence speaker ids (None entries fall
        back to the config speaker) — different speakers can share one
        device dispatch, which is what lets the continuous-batching
        scheduler coalesce requests from different voices' speakers.
        """
        if not phoneme_batches:
            return []
        sc = self.get_fallback_synthesis_config()
        with tracing.span("encode-ids") as sp:
            ids_list = [self._encode_phonemes(p) for p in phoneme_batches]
            sp.annotate(sentences=len(ids_list))
        n = len(ids_list)
        if speakers is not None and len(speakers) != n:
            raise OperationError(
                f"speakers list has {len(speakers)} entries for {n} sentences")
        if scales is not None and len(scales) != n:
            raise OperationError(
                f"scales list has {len(scales)} entries for {n} sentences")

        chunks = shape_plan.plan_dispatch_groups(
            [len(ids) for ids in ids_list],
            [sc.length_scale if scales is None or scales[i] is None
             else scales[i].length_scale for i in range(n)],
            min_batch=self.MIN_DISPATCH_BATCH,
            max_batch=self.MAX_DISPATCH_BATCH)

        def decode_groups():
            """``(input rows, ticket)`` of each back program, launched in
            this order.  Front programs run :data:`FRONT_LOOKAHEAD`
            batches ahead, so the device has the next batch's front to
            run while the host waits for this one's frame counts."""
            ahead: list[tuple[list[int], dict]] = []
            ci = 0
            while ci < len(chunks) or ahead:
                while ci < len(chunks) and len(ahead) < self.FRONT_LOOKAHEAD:
                    chunk = chunks[ci]
                    ci += 1
                    ahead.append((chunk, self._enqueue_front(
                        [ids_list[i] for i in chunk], sc,
                        speakers=([speakers[i] for i in chunk]
                                  if speakers is not None else None),
                        scales=([scales[i] for i in chunk]
                                if scales is not None else None))))
                chunk, front = ahead.pop(0)
                for group in self._plan_front(front):
                    yield ([chunk[r] for r in group.rows],
                           self._enqueue_back(front, group))

        # Pipelined dispatch: enqueue up to PIPELINE_DEPTH back programs
        # ahead, then fetch in order.  The chip computes group k+1 while
        # group k's result copies back to the host.
        wavs: list[Optional[np.ndarray]] = [None] * n
        lengths = [0] * n
        row_ms = [0.0] * n
        pending: list[tuple[list[int], dict]] = []
        t_last_drain = time.perf_counter()

        def drain_one():
            nonlocal t_last_drain
            rows, ticket = pending.pop(0)
            w, wl = self._finish_back(ticket)
            # honest per-dispatch timing: each row carries the wall time
            # attributable to the dispatch that produced it, amortized over
            # that dispatch's rows — not the whole batch's average (the
            # reference times each session.run, piper/src/lib.rs:361-380).
            # With pipelining the device runs dispatches serially, so a
            # ticket's interval starts at the later of its enqueue and the
            # previous drain — raw enqueue→result would double-count the
            # queue wait behind earlier in-flight groups.
            now = time.perf_counter()
            ms = (now - max(ticket["t_enqueue"], t_last_drain)) * 1000.0
            t_last_drain = now
            ms /= len(rows)
            for row, i in enumerate(rows):
                wavs[i] = w[row]
                lengths[i] = int(wl[row])
                row_ms[i] = ms

        # direct callers (no scheduler: the stock path) get their device
        # work as a "dispatch" span that opens the annotation channel
        # itself, so the groups below land in it and the scope counts the
        # dispatch once; under the batch scheduler, which holds the
        # channel and records the shared span, this is a no-op (the
        # worker thread carries no trace context)
        with tracing.dispatch_span(voice=self.scope_voice,
                                   sentences=n) as dispatch_sp:
            todo, programs = decode_groups(), 0
            while True:
                while len(pending) < self.PIPELINE_DEPTH:
                    launched = next(todo, None)
                    if launched is None:
                        break
                    pending.append(launched)
                    programs += 1
                if not pending:
                    break
                drain_one()
            dispatch_sp.annotate(groups=programs)

        info = self.audio_output_info()
        return [
            Audio(AudioSamples(np.asarray(wavs[i][: lengths[i]])), info,
                  inference_ms=row_ms[i])
            for i in range(n)
        ]

    # ------------------------------------------------------------------
    # staged inference
    # ------------------------------------------------------------------

    def _next_rng(self):
        with self._rng_lock:
            self._rng_counter += 1
            counter = self._rng_counter
        mixed = (self._seed * 0x9E3779B1 + counter) & 0xFFFFFFFF
        return jax.random.PRNGKey(np.uint32(mixed))

    def _scale_arrays(self, sc: SynthesisConfig, batch: int,
                      scales: "Optional[list[Optional[SynthesisConfig]]]"
                      = None):
        """Per-row (noise_w, length_scale, noise_scale) [B] arrays.

        ``scales`` entries override the shared config row-wise, letting a
        coalesced batch carry each request's own synthesis scales."""
        def row(i, attr):
            if scales is not None and i < len(scales) and scales[i] is not None:
                return float(getattr(scales[i], attr))
            return float(getattr(sc, attr))

        nw = [row(i, "noise_w") for i in range(batch)]
        ls = [row(i, "length_scale") for i in range(batch)]
        ns = [row(i, "noise_scale") for i in range(batch)]
        # host lists returned alongside the device arrays so callers can do
        # host-side math (frame estimation) without a device round trip
        return (jnp.asarray(nw, jnp.float32), jnp.asarray(ls, jnp.float32),
                jnp.asarray(ns, jnp.float32), ls)

    def _sid_array(self, sc: SynthesisConfig, batch: int,
                   speakers: Optional[list[Optional[int]]] = None):
        if not self.multi_speaker:
            # single-speaker voice: only speaker 0 (or None) is honorable —
            # silently producing default-voice audio for another id would
            # hide a caller bug
            for sid in speakers or []:
                if sid not in (None, 0):
                    raise OperationError(
                        f"speaker id {sid} requested on a single-speaker "
                        "voice")
            return None
        default = sc.speaker[1] if sc.speaker else 0
        rows = [default if s is None else s
                for s in (speakers or [])] or [default]
        rows = rows + [default] * (batch - len(rows))
        for sid in rows:
            if not 0 <= sid < self.config.num_speakers:
                # JAX gather would silently clamp an out-of-range id
                raise OperationError(
                    f"speaker id {sid} out of range "
                    f"(voice has {self.config.num_speakers} speakers)")
        return jnp.asarray(rows[:batch], dtype=jnp.int32)

    def _jit(self, run, batch_args: tuple[int, ...],
             n_args: Optional[int] = None):
        """jit, adding mesh shardings when a mesh is attached.

        ``batch_args``: positional indices of [B, ...]-shaped arguments
        (sharded on the data axis); ``n_args``: how many arguments a call
        passes, where ``run``'s signature does not say (``*sid``).
        Params, RNG keys, and scalars are replicated; every output is
        batch-major and data-sharded.  XLA then runs the whole stage SPMD
        across chips with no code changes — this is the TPU counterpart of
        the reference's rayon fan-out (``synth/src/lib.rs:316-320``).
        """
        if self.mesh is None:
            return jax.jit(run)
        import inspect

        from ..parallel.mesh import (
            data_sharding, param_shardings, replicated)

        ds, rep = data_sharding(self.mesh), replicated(self.mesh)
        # arg 0 is always the params pytree: its per-leaf shardings carry
        # the tensor-parallel decoder annotations (model axis); plain
        # replication when model_parallel == 1
        ps = param_shardings(self.mesh, self.params)
        if n_args is None:
            n_args = len(inspect.signature(run).parameters)
        in_shardings = tuple(
            ps if i == 0 else (ds if i in batch_args else rep)
            for i in range(n_args))
        return jax.jit(run, in_shardings=in_shardings, out_shardings=ds)

    def _encode_fn(self, b: int, t: int):
        """Jitted stage 1 for batch/text bucket (b, t)."""
        key = (b, t)
        with self._jit_lock:
            fn = self._enc_cache.get(key)
            if fn is None:
                hp = self.hp

                mesh = self.mesh  # seq>1 ⇒ ring-attention text encoder

                if self.multi_speaker:
                    def run(params, ids, lens, rng, noise_w, length_scale, sid):
                        m_p, logs_p, w_ceil, x_mask, _ = vits.encode_text(
                            params, hp, ids, lens, rng, noise_w=noise_w,
                            length_scale=length_scale, sid=sid, mesh=mesh)
                        return m_p, logs_p, w_ceil, x_mask
                else:
                    def run(params, ids, lens, rng, noise_w, length_scale):
                        m_p, logs_p, w_ceil, x_mask, _ = vits.encode_text(
                            params, hp, ids, lens, rng, noise_w=noise_w,
                            length_scale=length_scale, mesh=mesh)
                        return m_p, logs_p, w_ceil, x_mask

                batch = ((1, 2, 4, 5, 6) if self.multi_speaker
                         else (1, 2, 4, 5))
                fn = self._jit(run, batch)
                self._enc_cache[key] = fn
        return fn

    def _acoustics_fn(self, b: int, t: int, f: int):
        """Jitted stage 2 alone (streaming path: keep z on device)."""
        with self._jit_lock:
            fn = self._aco_cache.get(f)
            if fn is None:
                hp = self.hp
                max_frames = f
                mesh = self.mesh

                def body(params, m_p, logs_p, w_ceil, x_mask, rng,
                         noise_scale, g):
                    z, y_mask, y_lengths = vits.acoustics(
                        params, hp, m_p, logs_p, w_ceil, x_mask, rng,
                        noise_scale=noise_scale, max_frames=max_frames, g=g,
                        mesh=mesh)
                    return z, y_lengths

                # signature arity must match the call exactly so that mesh
                # in_shardings line up positionally
                if self.multi_speaker:
                    def run(params, m_p, logs_p, w_ceil, x_mask, rng,
                            noise_scale, sid):
                        g = params["emb_g"][sid][:, None, :]
                        return body(params, m_p, logs_p, w_ceil, x_mask, rng,
                                    noise_scale, g)

                    batch = (1, 2, 3, 4, 6, 7)
                else:
                    def run(params, m_p, logs_p, w_ceil, x_mask, rng,
                            noise_scale):
                        return body(params, m_p, logs_p, w_ceil, x_mask, rng,
                                    noise_scale, None)

                    batch = (1, 2, 3, 4, 6)
                fn = self._jit(run, batch)
                self._aco_cache[f] = fn
        return fn

    def _front_fn(self, b: int, t: int):
        """Jitted front program of the stock path at (b, t): text encoder
        and duration predictor.  What it returns stays on the device for
        the back programs, but for the last output: each row's frame
        count (``int32[b]``), which is all the dispatch plan reads."""
        key = (b, t)
        with self._jit_lock:
            fn = self._front_cache.get(key)
            if fn is None:
                hp = self.hp
                mesh = self.mesh  # seq>1 ⇒ ring-attention text encoder

                def run(params, ids, lens, rng, noise_w, length_scale, *sid):
                    rng_dur, _ = jax.random.split(rng)
                    m_p, logs_p, w_ceil, x_mask, _ = vits.encode_text(
                        params, hp, ids, lens, rng_dur, noise_w=noise_w,
                        length_scale=length_scale,
                        sid=sid[0] if sid else None, mesh=mesh)
                    frames_needed = jnp.sum(w_ceil, axis=1).astype(jnp.int32)
                    return m_p, logs_p, w_ceil, x_mask, frames_needed

                fn = self._jit(run, (1, 2, 4, 5, 6)[:4 + self.multi_speaker],
                               n_args=6 + self.multi_speaker)
                self._front_cache[key] = fn
        return fn

    def _back_fn(self, b: int, t: int, f: int):
        """Jitted back program at (b, t, f): the front's outputs for ``b``
        rows → int16 audio (acoustics, decoder, quantization), with the
        frame bucket ``f`` that the plan read off the rows' true frame
        counts.  ``rows`` names each row's index in its front batch: it
        keys the row's noise, so a row's audio does not depend on the
        group the plan put it in.  ``rng`` is the front's key, split the
        same way."""
        key = (b, t, f)
        with self._jit_lock:
            fn = self._back_cache.get(key)
            if fn is None:
                hp = self.hp
                max_frames = f
                mesh = self.mesh
                cdt = self.compute_dtype

                def run(params, m_p, logs_p, w_ceil, x_mask, rows, rng,
                        noise_scale, *sid):
                    _, rng_noise = jax.random.split(rng)
                    g = params["emb_g"][sid[0]][:, None, :] if sid else None
                    z, y_mask, y_lengths = vits.acoustics(
                        params, hp, m_p, logs_p, w_ceil, x_mask, rng_noise,
                        noise_scale=noise_scale, max_frames=max_frames, g=g,
                        mesh=mesh, rows=rows)
                    return decode_opts.decode_quantize(
                        params, hp, z, y_lengths, g, mesh=mesh,
                        compute_dtype=cdt)

                fn = self._jit(
                    run, (1, 2, 3, 4, 5, 7, 8)[:6 + self.multi_speaker],
                    n_args=8 + self.multi_speaker)
                self._back_cache[key] = fn
        return fn

    def _decode_window_fn(self, width: int):
        """Jitted chunk decoder: z window of static ``width`` → samples."""
        key = width
        with self._jit_lock:
            fn = self._dec_cache.get(key)
            if fn is None:
                hp = self.hp
                cdt = self.compute_dtype

                def run(params, z, start, sid=None):
                    g = (params["emb_g"][sid][:, None, :]
                         if sid is not None else None)
                    window = jax.lax.dynamic_slice_in_dim(z, start, width,
                                                          axis=1)
                    return vits.decode(params, hp, window, g=g,
                                       compute_dtype=cdt)

                fn = jax.jit(run)
                self._dec_cache[key] = fn
        return fn

    def _decode_windows_batch_fn(self, width: int, b: int, has_sid: bool):
        """Jitted batched chunk decoder for coalesced concurrent streams:
        stacked pre-sliced z windows [B, width, C] → [B, width*hop].

        Windows are sliced out of each stream's z *before* they reach this
        function (coalescer ``submit``), so the executable's shape depends
        only on (width, b, has_sid) — NOT on each utterance's frame
        bucket.  That keeps the compiled-shape set small and fully
        prewarmable; the first round of concurrent traffic must never pay
        a mid-request XLA compile (a cold shape stalls every stream
        riding that dispatch for the length of the compile)."""
        # never donated: the stacked [B, width, C] windows buffer is dead
        # after the call, but XLA input/output aliasing needs an
        # identically-sized output to reuse it and the [B, width*hop]
        # waveform never matches
        key = self._wdec_cache_key(width, b, has_sid, fused=False)
        with self._jit_lock:
            fn = self._dec_cache.get(key)
            if fn is None:
                hp = self.hp
                cdt = self.compute_dtype

                def run(params, windows, sid=None):
                    g = (params["emb_g"][sid][:, None, :]
                         if sid is not None else None)
                    return vits.decode(params, hp, windows, g=g,
                                       compute_dtype=cdt)

                fn = jax.jit(run)
                self._dec_cache[key] = fn
        return fn

    def _decode_windows_fused_fn(self, width: int, b: int, has_sid: bool):
        """Fused-epilogue variant of :meth:`_decode_windows_batch_fn`
        (``SONATA_FUSED_EPILOGUE=lax``): window decode +
        crossfade taper + peak-scaled i16 quantize as ONE device
        program.

        Extra args ``lo``/``hi`` [B] are each row's emitted sample range
        (value-dynamic, shape-static — the executable set stays one per
        (width, batch rung), exactly like the unfused fn, so the warmup
        lattice covers it).  Returns (i16 [B, width*hop], peak [B]); the
        host dequantizes and slices instead of tapering — the per-chunk
        epilogue leaves the TTFB path, and the result transfer halves
        (i16 + per-row peak instead of f32)."""
        key = self._wdec_cache_key(width, b, has_sid, fused=True)
        with self._jit_lock:
            fn = self._dec_cache.get(key)
            if fn is None:
                hp = self.hp
                cdt = self.compute_dtype

                def run(params, windows, lo, hi, sid=None):
                    g = (params["emb_g"][sid][:, None, :]
                         if sid is not None else None)
                    wav = vits.decode(params, hp, windows, g=g,
                                      compute_dtype=cdt)
                    return decode_opts.fused_epilogue(
                        wav, lo, hi, CROSSFADE_SAMPLES)

                fn = jax.jit(run)
                self._dec_cache[key] = fn
        return fn

    def _wdec_cache_key(self, width: int, b: int, has_sid: bool,
                        fused: Optional[bool] = None) -> tuple:
        """The decode-cache key live window-decode traffic dispatches
        through for this (width, batch, sid) shape — fused when the
        epilogue arm is on (the default), the plain batch decoder
        otherwise.  The single place warmup, attribution, and tests
        resolve the active variant."""
        if fused is None:
            fused = self.fused_epilogue != "off"
        if fused:
            return ("wfused", width, b, has_sid, self.fused_epilogue)
        return ("wbatch", width, b, has_sid)

    @property
    def dispatch_policy(self) -> DispatchPolicy:
        """The resolved backend-adaptive dispatch policy (lazy, cached).

        Resolution order: an explicitly-passed policy > env overrides
        (``SONATA_DISPATCH_POLICY``) > the
        backend fast path / cached dispatch-scaling probe — see
        :func:`sonata_tpu.utils.dispatch_policy.resolve_policy`.
        Resolved outside the jit lock: the probe may itself dispatch.
        """
        with self._policy_lock:
            if self._dispatch_policy is None:
                self._dispatch_policy = resolve_policy(
                    shape_key=(self.hp.inter_channels, self.hp.hop_length))
                import logging

                logging.getLogger("sonata").info(
                    self._dispatch_policy.describe())
            return self._dispatch_policy

    def dispatch_stats(self) -> dict:
        """Per-dispatch observability: the policy decision plus each
        stream coalescer's request/dispatch counters and coalescing
        ratio (requests per device dispatch; 1.0 = no coalescing).
        Stages that never ran report ``None``."""
        def view(co):
            if co is None:
                return None
            s = dict(co.stats)
            s["coalescing_ratio"] = round(
                s["requests"] / max(s["dispatches"], 1), 3)
            return s

        with self._jit_lock:
            decode, stage = self._stream_coalescer, self._stage_coalescer
            iteration = self._iter_decoder
        pol = self._dispatch_policy
        try:
            mode = resolve_batch_mode(pol)
        except OperationError:
            mode = None  # typo'd SONATA_BATCH_MODE fails at stream time
        return {"policy": pol.as_dict() if pol is not None else None,
                "batch_mode": mode,
                "stream_decode": view(decode),
                "stream_stage": view(stage),
                "iteration": view(iteration)}

    @property
    def _stream_decoder(self):
        """The active window-decode engine for NEW streams.

        ``SONATA_BATCH_MODE`` (default: iteration iff the PR-1 dispatch
        policy kept coalescing) picks between the dispatch-granular
        coalescer and the persistent iteration loop; the degradation
        ladder can force iteration back to dispatch at level >= 1
        (consulted per stream, so recovery re-admits the loop with no
        restart).  Both engines can exist at once — streams resident in
        the loop finish there while degraded traffic takes the wave
        path."""
        policy = self.dispatch_policy
        mode = effective_batch_mode(policy)
        kwargs = policy.stream_decode_kwargs()
        with self._jit_lock:
            if self._voice_closed:
                raise OperationError(
                    "voice is closed; streaming is unavailable")
            if mode == "iteration":
                if self._iter_decoder is None:
                    # an env-forced iteration mode on a per-request
                    # policy (batch 1) still wants a real batch axis —
                    # the loop exists to share iterations across
                    # streams, so take the canonical coalescing batch
                    b = kwargs["max_batch"]
                    if b <= 1:
                        b = COALESCING_DEFAULTS["stream_decode_max_batch"]
                    engines = _stream_engines()
                    self._iter_decoder = engines._IterationStreamDecoder(
                        self, max_batch=b)
                return self._iter_decoder
            if self._stream_coalescer is None:
                engines = _stream_engines()
                self._stream_coalescer = engines._StreamDecodeCoalescer(
                    self, **kwargs)
            return self._stream_coalescer

    @property
    def _stream_stages(self):
        kwargs = self.dispatch_policy.stream_stage_kwargs()
        with self._jit_lock:
            if self._voice_closed:
                raise OperationError(
                    "voice is closed; streaming is unavailable")
            if self._stage_coalescer is None:
                engines = _stream_engines()
                self._stage_coalescer = engines._StreamStageCoalescer(
                    self, **kwargs)
            return self._stage_coalescer

    def start_draining(self) -> None:
        """Graceful-drain hook (the frontends call this alongside
        ``ReplicaPool.start_draining`` before voice teardown): the
        iteration loop stops admitting NEW stream joins — refused typed
        ``draining`` — while resident streams keep their riders until
        they finish; the loop then exits at an iteration boundary.  The
        dispatch-mode coalescers need no equivalent (they hold no
        resident state; close() drains them).  Idempotent."""
        with self._jit_lock:
            iteration = self._iter_decoder
        if iteration is not None:
            iteration.start_draining()

    def close(self) -> None:
        """Unload the voice: stop the coalescer threads and fail their
        queued work.

        The reference's `libsonataUnloadSonataVoice`
        (``capi/src/lib.rs:228``) drops the model; here the voice also
        owns four lazily-spawned daemon threads, which without an explicit
        close linger up to one 5 s poll interval after the last reference
        drops.  Idempotent; a closed voice can still synthesize
        non-streaming batches (the coalescers are streaming-only), but
        any further STREAMING raises OperationError — close() is terminal
        for the coalescers, never respawning their threads."""
        with self._jit_lock:
            self._voice_closed = True
            decoder, self._stream_coalescer = self._stream_coalescer, None
            stages, self._stage_coalescer = self._stage_coalescer, None
            iteration, self._iter_decoder = self._iter_decoder, None
        if decoder is not None:
            decoder.close()
        if stages is not None:
            stages.close()
        if iteration is not None:
            iteration.close()

    def _batch_bucket(self, n_rows: int) -> int:
        """The padded batch ``n_rows`` rows run at: the batch ladder's
        step, rounded up to a multiple of the mesh's data axis so the
        batch shards evenly on any mesh (non-power-of-two included)."""
        b = bucket_for(n_rows, BATCH_BUCKETS)
        if self.mesh is not None:
            from ..parallel.mesh import DATA_AXIS

            d = self.mesh.shape[DATA_AXIS]
            b = ((max(b, d) + d - 1) // d) * d
        return b

    def _identity_rows(self, b: int):
        """``arange(b)`` on the device, made once per batch size: the row
        indices of a front batch that runs whole as one decode group (the
        launch that follows the plan is the one stretch of host time the
        device may have to wait for)."""
        rows = self._row_iota.get(b)
        if rows is None:
            rows = self._row_iota[b] = jnp.asarray(
                np.arange(b, dtype=np.int32))
        return rows

    def _gather_fn(self):
        """The jitted gather of a decode group's rows from its front
        batch's staged arrays, on the device (one object: jit keys its
        own cache by the shapes).  On a mesh what it returns is sharded by
        rows, as the back program takes it."""
        def run(staged, rows):
            return tuple(a[rows] for a in staged)

        if self.mesh is None:
            return jax.jit(run)
        from ..parallel.mesh import data_sharding

        return jax.jit(run, out_shardings=data_sharding(self.mesh))

    def _pad_batch(self, ids_list: list[list[int]]):
        """Pad a sentence batch to (batch, text) buckets.

        Both axes are bucketed so the number of compiled executables stays
        bounded under arbitrary workloads; dummy rows are masked out by
        their length-1 semantics and dropped by callers.
        """
        n_real = len(ids_list)
        b = self._batch_bucket(n_real)
        t = bucket_for(max(len(i) for i in ids_list), TEXT_BUCKETS)
        padded = ids_list + [[0]] * (b - n_real)
        ids = jnp.asarray([pad_to(i, t) for i in padded], dtype=jnp.int32)
        lens = jnp.asarray([len(i) for i in ids_list] + [1] * (b - n_real),
                           dtype=jnp.int32)
        return ids, lens, b, t

    def _run_encode(self, ids_list: list[list[int]], sc: SynthesisConfig):
        """Run stage 1 on a padded batch (streaming path)."""
        ids, lens, b, t = self._pad_batch(ids_list)
        sid = self._sid_array(sc, b)
        nw, ls, _, _ = self._scale_arrays(sc, b)
        args = [self.params, ids, lens, self._next_rng(), nw, ls]
        if sid is not None:
            args.append(sid)
        m_p, logs_p, w_ceil, x_mask = self._encode_fn(b, t)(*args)
        return m_p, logs_p, w_ceil, x_mask, sid, b, t

    def _enqueue_front(self, ids_list: list[list[int]], sc: SynthesisConfig,
                       speakers: Optional[list[Optional[int]]] = None,
                       scales: "Optional[list[Optional[SynthesisConfig]]]"
                       = None) -> dict:
        """Asynchronously launch one front batch; returns the ticket that
        :meth:`_plan_front` and :meth:`_enqueue_back` read.  Only the
        frame counts are prefetched to the host."""
        t_start = time.perf_counter()
        n_real = len(ids_list)
        with tracing.annotation("front"), tracing.compile_sink() as paid:
            ids, lens, b, t = self._pad_batch(ids_list)
            sid = self._sid_array(sc, b, speakers)
            nw, ls, ns, ls_host = self._scale_arrays(sc, b, scales)
            # one key for the front and every back program of this batch
            rng = self._next_rng()
            args = [self.params, ids, lens, rng, nw, ls]
            if sid is not None:
                args.append(sid)
            with self._first_call(("front", b, t)):
                *staged, frames = self._front_fn(b, t)(*args)   # async
            prefetch_to_host(frames)
        t_enqueue = time.perf_counter()
        # what the back programs gather their rows from: the front's
        # outputs and the per-row arguments, all on the device
        staged += [ns] if sid is None else [ns, sid]
        return {"staged": tuple(staged), "frames": frames, "rng": rng,
                "b": b, "t": t, "n_real": n_real,
                "max_ids": max(len(row) for row in ids_list),
                "weighted_ids": float(max(
                    len(row) * max(ls_host[i], shape_plan.MIN_LENGTH_SCALE)
                    for i, row in enumerate(ids_list))),
                # non-default length scales change the frames a text
                # bucket can need, so their shapes sit OUTSIDE the warmup
                # lattice's coverage promise: flagged so the scope's
                # cold-compile containment doesn't report a legitimate
                # scaled request as a coverage regression
                "scaled": any(abs(l - sc.length_scale) > 1e-9
                              for l in ls_host[:n_real]),
                "t_enqueue": t_enqueue,
                # the front's host work, what its launch took from the
                # host and what compiled under it, for the first of its
                # groups' records to carry
                "paid": paid,
                "enqueue_ms": (t_enqueue - t_start) * 1e3,
                "upload_bytes": self._weights_host_bytes + ns.nbytes + sum(
                    a.nbytes for a in args[1:])}

    def _plan_front(self, front: dict) -> "list[shape_plan.DecodeGroup]":
        """Wait for a front batch's frame counts (the one host sync of the
        stock path) and cut its rows into decode groups."""
        with tracing.annotation("plan"):
            frames = jax.device_get(front["frames"])[:front["n_real"]]
        front["front_wait_ms"] = (
            time.perf_counter() - front["t_enqueue"]) * 1e3
        front["frames_needed"] = frames.tolist()
        self.frame_estimator.observe(front["weighted_ids"],
                                     int(frames.max()))
        t = front["t"]
        with self._jit_lock:
            warm = {(b, f) for (b, t_, f) in self._back_warm if t_ == t}
        families = {b for b, _ in warm}
        # a batch size that has compiled nothing yet at this text bucket
        # counts as warm: its first program compiles with its companions
        # while whoever needs them waits (_compile_back), which is how a
        # voice comes by the programs a cut runs as.  After that a cut
        # takes what is compiled
        return shape_plan.plan_decode_groups(
            front["frames_needed"], min_rows=shape_plan.DECODE_MIN_ROWS,
            max_rows=self.MAX_DISPATCH_BATCH,
            program_cost=shape_plan.DECODE_PROGRAM_COST,
            batch_for=self._batch_bucket,
            is_warm=lambda b, f: (b, f) in warm or b not in families)

    def _enqueue_back(self, front: dict,
                      group: "shape_plan.DecodeGroup") -> dict:
        """Asynchronously launch one decode group of a front batch;
        returns a ticket for :meth:`_finish_back`.  Split from the fetch
        so ``speak_batch`` keeps several programs in flight."""
        t_start = time.perf_counter()
        b, t, f = group.batch, front["t"], group.frame_bucket
        n_real = len(group.rows)
        needs = [front["frames_needed"][r] for r in group.rows]
        # what compiles on this thread under the launch (the program on a
        # cold shape, an eager operation's small one on its first use) is
        # this record's: ``compile_ms`` and ``compiled`` beside ``compile``
        with tracing.annotation("enqueue"), tracing.compile_sink() as paid:
            staged = front["staged"]
            if b == front["b"] and group.rows == list(range(n_real)):
                rows = self._identity_rows(b)   # the batch whole: no gather
            else:
                # dummy rows repeat the group's first row: they fit the
                # bucket and are dropped at the fetch.  The gather runs on
                # the device: the front's outputs never pass the host
                rows = jnp.asarray(
                    group.rows + group.rows[:1] * (b - n_real), jnp.int32)
                staged = self._gather_rows(staged, rows)
            args = [self.params, *staged[:4], rows, front["rng"],
                    *staged[4:]]
            cached = (b, t, f) in self._back_warm
            # the one record of this device program, for whoever holds
            # the dispatch channel (the batch scheduler around
            # speak_batch, or speak_batch itself on the stock path) and
            # for the always-on counters: the padded shape this group
            # actually ran at, what the padding cost, and whether this
            # shape paid an XLA compile — the single biggest TTFB outlier
            # cause.  Group-wise: one speak_batch may issue several device
            # programs, and a cold group must never be shadowed by a later
            # cached one.
            record = tracing.annotate_dispatch_group(
                batch_bucket=b, text_bucket=t, frame_bucket=f, rows=n_real,
                padding_rows=b - n_real,
                padding_ratio=round((b - n_real) / b, 3),
                compile="cached" if cached else "cold",
                # "warm_above": the exact bucket's program was cold and
                # the plan took the nearest warm one above it
                plan=("exact" if f == bucket_for(max(needs), FRAME_BUCKETS)
                      else "warm_above"),
                **({"scaled": True} if front["scaled"] else {}))
            t_launch = time.perf_counter()
            out = (self._back_fn(b, t, f)(*args) if cached  # async dispatch
                   else self._compile_back(
                       (b, t, f), args,
                       max(front["frames_needed"]) / front["max_ids"]))
            prefetch_to_host(out)
        t_enqueue = time.perf_counter()
        # what the headline does not aggregate stays on the group: how
        # much of the enqueue was the jitted call itself, which is
        # asynchronous only while the runtime has a free slot for one
        # more program in flight (and compiles, on a cold shape); what is
        # static in the compiled shape: per upsample stage, the time
        # steps folded into the channel axis; what this launch handed
        # over from the host (sizes from shapes: nothing waits on the
        # device).  A front batch's own launch, upload and the wait for
        # its frame counts are carried by the first of its groups
        record.update(
            frames_needed=needs,
            frames_per_id=round(self.frame_estimator.frames_per_id, 4),
            decode_fold=vits.decode_fold(self.params["dec"], self.hp, f,
                                         self.mesh),
            upload_bytes=(self._weights_host_bytes
                          + (0 if staged is front["staged"] else rows.nbytes)
                          + front.pop("upload_bytes", 0)),
            front_wait_ms=round(front.pop("front_wait_ms", 0.0), 3),
            enqueue_ms=round((t_enqueue - t_start) * 1e3
                             + front.pop("enqueue_ms", 0.0), 3),
            launch_ms=round((t_enqueue - t_launch) * 1e3, 3),
            **tracing.compile_attrs(front.pop("paid", []) + paid))
        return {"out": out, "n_real": n_real, "t_enqueue": t_enqueue,
                "group": record}

    def _finish_back(self, ticket: dict):
        """Fetch a ticket's result and dequantize it."""
        record, n_real = ticket["group"], ticket["n_real"]
        t_start = time.perf_counter()
        with tracing.annotation("fetch"):
            # one batched fetch: device_get coalesces the per-array copies
            wav_i16, wav_lengths, peaks = jax.device_get(ticket["out"])
        t_fetched = time.perf_counter()
        with tracing.annotation("epilogue"):
            wav_i16 = wav_i16[:n_real]
            peaks = np.maximum(peaks[:n_real, None], 0.01)
            # dequantize back to the model's original amplitudes
            wav = wav_i16.astype(np.float32) * (peaks / 32767.0)
        t_done = time.perf_counter()
        record.update(fetch_wait_ms=round((t_fetched - t_start) * 1e3, 3),
                      epilogue_ms=round((t_done - t_fetched) * 1e3, 3))
        tracing.record_device_group(record, self.scope_voice)
        return wav, wav_lengths[:n_real]

    def _compile_back(self, shape: tuple[int, int, int], args: list,
                      frames_per_id: float):
        """The first call of a back program, which compiles it.  The first
        program of a (b, t) compiles with its companions
        (:func:`.shape_plan.companion_shapes`: the frame buckets this text
        bucket's rows can need at ``frames_per_id``): the request that
        waits here is slow already.  Callers that meet the cold (b, t)
        together wait for all of it, so a boot's compiles are over when
        its first requests return: a compile that holds one caller while
        the others run is what a warm-up that watches request times
        cannot see."""
        b, t, f = shape
        with self._first_call(("back", b, t)) as first_of_its_text_bucket:
            with self._first_call(shape):
                out = self._back_fn(b, t, f)(*args)
            with self._jit_lock:
                self._back_warm.add(shape)
            if first_of_its_text_bucket:
                def warm(companion):
                    try:
                        self._warm_triple(companion, aot=False)
                    except Exception as e:  # the request has its own shape
                        import logging

                        logging.getLogger("sonata").warning(
                            "warm of %s beside %s failed (continuing): %s",
                            companion, shape, e)

                # compiles of distinct shapes do not depend on each other
                # (prewarm: four threads roughly halve a cold boot's)
                with ThreadPoolExecutor(
                        4, thread_name_prefix="sonata_warm") as ex:
                    list(ex.map(warm, shape_plan.companion_shapes(
                        shape, frames_per_id)))
        return out

    @contextlib.contextmanager
    def _first_call(self, key: tuple):
        """Around a call that compiles the first time: yields True to the
        first caller for ``key`` and False to every other, who waits until
        the first has left the block.  Callers that meet a cold program
        together so pay one compile, not one each (three callers' first
        requests otherwise do, at every boot)."""
        with self._jit_lock:
            done = self._first_calls.get(key)
            first = done is None
            if first:
                done = self._first_calls[key] = threading.Event()
        if not first:
            done.wait()
            yield False
            return
        try:
            yield True
        finally:
            done.set()

    # ------------------------------------------------------------------
    # streaming (reference stream_synthesis, piper/src/lib.rs:652-668)
    # ------------------------------------------------------------------

    def stream_synthesis(self, phonemes: str, chunk_size: int,
                         chunk_padding: int,
                         deadline=None) -> Iterator[Audio]:
        """``deadline``: optional per-request
        :class:`~sonata_tpu.serving.deadlines.Deadline` — in iteration
        mode the resident stream carries it, so expiry mid-flight fails
        *this* stream at an iteration boundary without touching its
        batch peers."""
        sc = self.get_fallback_synthesis_config()
        with tracing.span("encode-ids"):
            ids = self._encode_phonemes(phonemes)
        info = self.audio_output_info()
        hop = self.hp.hop_length

        t_enc0 = time.perf_counter()
        # encode + acoustics ride the shared stage coalescer: N streams
        # starting within the wait window become ONE batched encode and
        # ONE batched acoustics dispatch (the reference gives each stream
        # its own blocking session, grpc/src/main.rs:381-409 — linear
        # degradation under load; here the device sees a batch)
        with tracing.span("encode-acoustics") as enc_sp:
            z_row, total_frames, f, sid0 = self._stream_stages.start(ids,
                                                                     sc)
            enc_sp.annotate(frame_bucket=f)
        total_frames = min(total_frames, f)
        enc_ms = (time.perf_counter() - t_enc0) * 1000.0

        # the encode landed: this stream's window decodes join the
        # device's running batch (iteration mode) or the wave coalescer
        # (dispatch mode); one engine resolved per stream, so a ladder
        # flip mid-stream cannot split a stream across engines
        decoder = self._stream_decoder
        join = getattr(decoder, "join", None)
        handle = join(deadline) if join is not None else None

        # window decodes are independent given z, so they pipeline through
        # the engine (and batch with other streams') while the consumer
        # drains chunk by chunk — but only a bounded look-ahead is in
        # flight: a stream abandoned early (gRPC client cancel drops the
        # generator) then wastes at most LOOKAHEAD window decodes and
        # batch slots instead of decoding its whole tail on-device.
        LOOKAHEAD = 3
        plans = list(plan_chunks(total_frames, chunk_size, chunk_padding))

        # fused decode epilogue (default): the crossfade taper and the
        # i16 quantize ride the decode's device program — the host only
        # dequantizes and slices, so the per-chunk epilogue leaves the
        # TTFB path and the D2H transfer halves
        fused = self.fused_epilogue != "off"

        def submit(plan):
            width = bucket_for(plan.width, FRAME_BUCKETS)
            start = min(plan.win_start, max(f - width, 0))
            shift = plan.win_start - start  # window moved left by pad
            lo = (shift + plan.trim_left) * hop
            hi = (shift + plan.width - plan.trim_right) * hop
            return (plan, start, width, lo, hi,
                    decoder.submit(z_row, start, width, sid0,
                                   stream=handle,
                                   epilogue=(lo, hi) if fused else None))

        try:
            submitted = [submit(p) for p in plans[:LOOKAHEAD]]
            next_i = len(submitted)
            while submitted:
                plan, start, width, lo, hi, fut = submitted.pop(0)
                t0 = time.perf_counter()
                with tracing.span("decode-window", width=width):
                    out = fut.result()
                if fused:
                    q, peak = out
                    # slice BEFORE dequantizing: the device zeroed
                    # everything outside [lo, hi), so the float work
                    # stays proportional to the emitted chunk
                    samples = AudioSamples(
                        decode_opts.dequantize_chunk(q[lo:hi], peak))
                    # taper already applied on device
                else:
                    samples = AudioSamples(out[lo:hi])
                    samples.crossfade(CROSSFADE_SAMPLES)  # taper (:838)
                ms = (time.perf_counter() - t0) * 1000.0 + enc_ms
                enc_ms = 0.0  # encoder cost attributed to the first chunk
                if next_i < len(plans):  # top up look-ahead before yield
                    submitted.append(submit(plans[next_i]))
                    next_i += 1
                yield Audio(samples, info, inference_ms=ms)
        finally:
            # stream end OR abandonment (gRPC cancel closes the
            # generator): retire from the running batch at the next
            # iteration boundary; pending look-ahead rows are cancelled
            if handle is not None:
                decoder.retire(handle)


def _stream_engines():
    """The stream engines' module, imported where an engine is first
    built (they import the batching core; the voice does not)."""
    from ..synth import stream_engines

    return stream_engines
