"""A unit voice: a backbone that generates acoustic units over a HiFi-GAN
vocoder.

The sentence's phoneme ids are the prompt; the backbone gives one
acoustic-unit id a frame; a unit table maps ids to the generator's latent;
and the HiFi-GAN generator a Piper voice runs (:func:`.vits.decode`,
time-folded stages and all) turns frames into samples, through the same
on-device int16 epilogue (:func:`.decode_opts.decode_quantize`).

**The backbone** is chosen by ``backbone["model_type"]``
(:data:`BACKBONES`): ``lfm2_moe`` (:mod:`.lfm2`) decodes one unit a row a
step through its key-value cache and convolution state; ``sdar_moe``
(:mod:`.sdar`) gives a block of units by denoising passes and a commit pass
over a block that sees itself whole; ``nemotron_h`` (:mod:`.nemotron_h`)
one unit a row a step through Mamba-2 states beside keys and values;
``pangu_ultra_moe`` (:mod:`.pangu_moe`) one through latent attention's one
cached row a position; ``laguna`` (:mod:`.laguna`) one through full layers
that keep every position of a slot beside window layers that keep a ring of
``sliding_window`` places; ``gigachat3_5`` (:mod:`.gigachat`) one through
delta-rule states, a matrix a head, beside a latent layer's rows.  Each
module holds its backbone whole:
configuration, layers, programs and the adapter (:mod:`.unit_backbone`'s
:class:`~.unit_backbone.Backbone`) that stands behind the engine surface
:class:`~sonata_tpu.synth.steploop.StepLoop` names and says what its cache
is (``describe``); nothing of the voice forks on a kind of backbone.

The voice JSON says so with ``"family": "unit_lm"``
(:func:`sonata_tpu.models.from_config_path`); beside Piper's keys
(``audio``, ``espeak``, ``inference``, ``phoneme_id_map``, ``model`` with
the generator's sizes) it holds ``backbone`` (the backbone's
``config.json``) and ``units``: ``first_id`` (ids below it are phoneme ids,
the others units), ``stop_id`` and ``frames_per_id``; for ``sdar_moe`` also
``mask_id``, ``block_length`` and ``denoising_steps`` (default 4).

**The length rule.**  A row gives exactly ``round(frames_per_id *
length_scale * ids)`` units: the sampler never gives the stop unit, and the
row ends at that count, which the host knows when the row joins (the
backbone says how many launches that takes: :meth:`plan`).  Sampling is
temperature ``noise_scale`` over the unit ids (0: greedy).

**Weights live on the device.**  A voice directory holds real tensors
(``tensors/<name>.bf16.npy`` / ``.f32.npy`` and ``generator.npz``, loaded
layer by layer); a process that has the weights already
(:func:`place_weights`, before the server's ``LoadVoice``) hands them over
placed.  Either way they are uploaded once, not per dispatch.

Every sentence of every request of a voice goes through the voice's one
:class:`~sonata_tpu.synth.steploop.StepLoop` (``SONATA_AR_SLOTS`` slots of
``SONATA_AR_POSITIONS`` positions).
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..audio import Audio, AudioSamples
from ..core import AudioInfo, BaseModel, FailedToLoadResource, \
    OperationError, Phonemes
from ..serving import tracing
from ..text.phonemizer import text_to_phonemes
from ..utils.buckets import FRAME_BUCKETS, TEXT_BUCKETS, bucket_for
from ..utils.transfer import prefetch_to_host
from . import decode_opts, gigachat, laguna, lfm2, nemotron_h, pangu_moe, \
    sdar
from .config import ModelConfig, SynthesisConfig
from .serialization import load_params, unflatten_params
from .unit_backbone import RowPlan
from .unit_layers import expert_matmul

FAMILY = "unit_lm"
SLOTS_ENV = "SONATA_AR_SLOTS"
POSITIONS_ENV = "SONATA_AR_POSITIONS"
DEFAULT_SLOTS = 64
DEFAULT_POSITIONS = 1024

#: weights a process placed on the device before the voice is loaded by
#: path (the server's ``LoadVoice`` knows only the path): resolved config
#: path -> ``{"backbone", "unit_table", "generator"}``, taken once
_PLACED: dict = {}
_PLACED_LOCK = threading.Lock()


def place_weights(config_path: Union[str, Path], weights: dict) -> None:
    """Hand a voice's weights, already on the device, to the loader: the
    next :meth:`UnitVoice.from_config_path` of this path takes them
    instead of reading tensors from the voice's directory."""
    with _PLACED_LOCK:
        _PLACED[str(Path(config_path).resolve())] = weights


def load_tensor(directory: Path, name: str):
    """One tensor of a voice directory, on the device in its storage
    type."""
    bf16 = directory / f"{name}.bf16.npy"
    if bf16.exists():
        bits = jnp.asarray(np.load(bf16, mmap_mode="r"))
        return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
    f32 = directory / f"{name}.f32.npy"
    if f32.exists():
        return jnp.asarray(np.load(f32, mmap_mode="r"))
    raise FailedToLoadResource(f"no tensor {name!r} under {directory}")


def load_weights(directory: Path, backbone) -> dict:
    """A voice directory's weights, one layer at a time."""
    tensors = directory / "tensors"

    def layer(i: int) -> dict:
        prefix = f"layers.{i}."
        names = sorted(p.name[len(prefix):].rsplit(".", 2)[0]
                       for p in tensors.glob(prefix + "*.npy"))
        if not names:
            raise FailedToLoadResource(f"no tensors of layer {i} under "
                                       f"{tensors}")
        return backbone.pack_layer(unflatten_params(
            {name.replace(".", "/"): load_tensor(tensors, prefix + name)
             for name in names}))

    generator = directory / "generator.npz"
    if not generator.exists():
        raise FailedToLoadResource(f"no generator weights at {generator}")
    params = {
        "embed": load_tensor(tensors, "embed"),
        "norm_f": load_tensor(tensors, "norm_f").astype(jnp.float32),
        "layers": [layer(i) for i in range(backbone.layers)]}
    if not backbone.cfg.tie_word_embeddings:
        params["head"] = load_tensor(tensors, "head")
    return {
        "backbone": params,
        "unit_table": load_tensor(tensors, "unit_table"),
        "generator": jax.device_put(load_params(generator))}


#: a backbone's adapter by its ``model_type``: the one line a backbone
#: costs here (the rest is its module's: configuration, layers, programs,
#: the adapter with its ``describe``)
BACKBONES = {"lfm2_moe": lfm2.Lfm2Backbone,
             "sdar_moe": sdar.SdarBackbone,
             "nemotron_h": nemotron_h.NemotronBackbone,
             "pangu_ultra_moe": pangu_moe.PanguBackbone,
             "laguna": laguna.LagunaBackbone,
             "gigachat3_5": gigachat.GigaChatBackbone}


def make_backbone(backbone: dict, units: dict, seed: int = 0):
    kind = backbone.get("model_type")
    if kind not in BACKBONES:
        raise ValueError(f"model_type {kind!r} is not one of "
                         f"{sorted(BACKBONES)}")
    return BACKBONES[kind](backbone, units, seed)


class UnitVoice(BaseModel):
    """A loaded unit voice: config, weights on the device, compiled
    programs and the step loop."""

    def __init__(self, config: ModelConfig, backbone: dict, units: dict,
                 weights: dict, *, seed: int = 0):
        self.config = config
        self.hp = config.hyper
        self.backbone = make_backbone(backbone, units, int(seed))
        self.cfg, self.units = self.backbone.cfg, self.backbone.units
        self.block_length = self.backbone.block_length
        self.denoising_steps = self.backbone.denoising_steps
        self.frames_per_id = float(units["frames_per_id"])
        vocab, mask = self.cfg.vocab_size, self.units.mask_id
        if not 0 < self.units.first_id <= self.units.stop_id < vocab or not (
                mask is None or self.units.first_id <= mask < vocab):
            raise OperationError(f"units {units} do not split a vocabulary "
                                 f"of {self.cfg.vocab_size}")
        # the two sizes every program's shape hangs on: the operator's,
        # read once, here
        self.slots = int(os.environ.get(SLOTS_ENV) or DEFAULT_SLOTS)
        self.positions = int(os.environ.get(POSITIONS_ENV)
                             or DEFAULT_POSITIONS)
        self.expert_layers = self.cfg.expert_layers
        #: what the step program's expert products run (a step group's
        #: span says it): known from the program's shape, before it is built
        self.expert_matmul = expert_matmul(
            self.cfg, self.slots * self.block_length, self.backbone.held)
        #: what reads the slots' keys and values (or latent rows) in the
        #: step program
        self.attention = self.backbone.attention(self.positions)
        #: what the slots' cache is, for the step loop and the spans
        self.description = self.backbone.describe(self.slots, self.positions)
        self.params = weights["backbone"]
        self.unit_table = weights["unit_table"]
        self.generator = {"dec": weights["generator"]["dec"]}
        if self.unit_table.shape != (self.cfg.vocab_size,
                                     self.hp.inter_channels):
            raise OperationError(
                f"unit table {self.unit_table.shape} is not vocabulary x "
                f"latent ({self.cfg.vocab_size}, {self.hp.inter_channels})")
        self._synth_lock = threading.Lock()
        self._synth_config = config.inference.copy()
        self._jit_lock = threading.Lock()
        self._programs: dict = {}
        self._warm_caches: Optional[threading.Semaphore] = None
        self._prefill_no = 0
        self.scope_voice: Optional[str] = None
        self._loop_lock = threading.Lock()
        self._loop = None    # the StepLoop, started by the first row
        self._closed = False

    # -- factories -----------------------------------------------------------
    @classmethod
    def from_config_path(cls, config_path: Union[str, Path], *, seed: int = 0,
                         mesh=None) -> "UnitVoice":
        if mesh is not None:
            raise OperationError("a unit voice holds one device's stage; "
                                 "it does not attach to a mesh")
        path = Path(config_path)
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, ValueError) as e:
            raise FailedToLoadResource(
                f"cannot load voice config {path}: {e}") from e
        config = ModelConfig.from_dict(data, path=path)
        try:
            backbone, units = data["backbone"], data["units"]
            built = make_backbone(backbone, units)
        except (KeyError, ValueError) as e:
            raise FailedToLoadResource(
                f"{path} is not a unit voice: {type(e).__name__}: {e}") from e
        with _PLACED_LOCK:
            weights = _PLACED.pop(str(path.resolve()), None)
        if weights is None:
            weights = load_weights(path.parent, built)
        return cls(config, backbone, units, weights, seed=seed)

    # -- Model protocol ------------------------------------------------------
    def audio_output_info(self) -> AudioInfo:
        return AudioInfo(sample_rate=self.config.sample_rate)

    def get_language(self) -> Optional[str]:
        return self.config.language or self.config.espeak_voice

    def properties(self) -> dict:
        return {"quality": self.config.quality or "unknown",
                "family": FAMILY}

    def get_default_synthesis_config(self) -> SynthesisConfig:
        return self.config.inference.copy()

    def get_fallback_synthesis_config(self) -> SynthesisConfig:
        with self._synth_lock:
            return self._synth_config.copy()

    def set_fallback_synthesis_config(self, config: Any) -> None:
        if not isinstance(config, SynthesisConfig):
            raise OperationError("invalid synthesis config type "
                                 f"{type(config).__name__}")
        with self._synth_lock:
            self._synth_config = config.copy()

    def phonemize_text(self, text: str) -> Phonemes:
        return text_to_phonemes(text, voice=self.config.espeak_voice,
                                remove_lang_switch_flags=True)

    def frame_budget(self, n_ids: int, length_scale: float = 1.0) -> int:
        """The units (frames) a sentence of ``n_ids`` phoneme ids is
        given: the length rule."""
        return max(1, round(self.frames_per_id * length_scale * n_ids))

    def speak_batch(self, phoneme_batches: list,
                    speakers: Optional[list] = None,
                    scales: Optional[list] = None) -> list:
        if not phoneme_batches:
            return []
        if speakers is not None and any(s is not None for s in speakers):
            raise OperationError("a unit voice has one speaker")
        n = len(phoneme_batches)
        if scales is not None and len(scales) != n:
            raise OperationError(
                f"scales list has {len(scales)} entries for {n} sentences")
        sc = self.get_fallback_synthesis_config()
        with tracing.span("encode-ids") as sp:
            ids_list = [self.config.phonemes_to_ids(p)
                        for p in phoneme_batches]
            sp.annotate(sentences=n)
        loop = self._step_loop()
        t0 = time.perf_counter()
        futures = []
        for i, ids in enumerate(ids_list):
            row_sc = (scales[i] if scales is not None
                      and scales[i] is not None else sc)
            budget = self.frame_budget(len(ids), row_sc.length_scale)
            if max(ids) >= self.units.first_id:
                raise OperationError("a phoneme id lies among the unit ids "
                                     f"(>= {self.units.first_id})")
            if self.backbone.positions_needed(len(ids), budget) \
                    > self.positions:
                raise OperationError(
                    f"a sentence of {len(ids)} phoneme ids and {budget} "
                    f"frames does not fit a slot of {self.positions} "
                    "positions")
            futures.append(loop.submit(ids, budget, row_sc.noise_scale))
        info = self.audio_output_info()
        audios = []
        for fut in futures:
            wav = fut.result()
            audios.append(Audio(
                AudioSamples(wav), info,
                inference_ms=(time.perf_counter() - t0) * 1e3 / n))
        return audios

    def speak_one_sentence(self, phonemes: str) -> Audio:
        return self.speak_batch([phonemes])[0]

    # -- lifetime ------------------------------------------------------------
    def _step_loop(self):
        from ..synth.steploop import StepLoop

        with self._loop_lock:
            if self._closed:
                raise OperationError("the voice is closed")
            if self._loop is None:
                self._loop = StepLoop(self, name=self.scope_voice or "")
            return self._loop

    def start_draining(self) -> None:
        with self._loop_lock:
            if self._loop is not None:
                self._loop.start_draining()

    def close(self) -> None:
        with self._loop_lock:
            self._closed = True
            loop, self._loop = self._loop, None
        if loop is not None:
            loop.close()

    # -- warm-up lattice (serving/warmup.py) ---------------------------------
    def lattice_shapes(self, mode: str = "full") -> list:
        """Every program a request can need: the step (and the gather of
        what a flagged row keeps of it), for each text bucket a slot can
        hold with its frames what admits a row of it (the step that carries
        its prompt, ``step_admit``, or where :meth:`carries` says no a
        ``prefill``), a vocoder for each frame bucket those give
        (``minimal``: the step alone; the rest compiles on first use).  The
        whole list, and not what some traffic happens to reach, because a
        first use compiles on the loop's own thread: every live row stands
        still for as long as it takes.  What fits a slot bounds it: at 1024
        positions the step, 7 admitting programs and 7 vocoders."""
        shapes = [("step",)]
        if mode == "full":
            texts = [t for t in TEXT_BUCKETS
                     if self._fits(self._fewest_ids(t))]
            longest = max(n for n in range(1, self.positions)
                          if self._fits(n))
            frames = sorted({bucket_for(self.frame_budget(n), FRAME_BUCKETS)
                             for n in range(1, longest + 1)})
            shapes += [("step_admit" if self.carries(self._fewest_ids(t))
                        else "prefill", t) for t in texts]
            shapes += [("vocode", f) for f in frames]
        return shapes

    def _fewest_ids(self, bucket: int) -> int:
        below = [t for t in TEXT_BUCKETS if t < bucket]
        return (below[-1] if below else 0) + 1

    def _fits(self, n_ids: int) -> bool:
        return self.backbone.positions_needed(
            n_ids, self.frame_budget(n_ids)) <= self.positions

    def warm_shape(self, shape: tuple) -> None:
        """Compile one program of :meth:`lattice_shapes`: a dummy dispatch
        through the jit cache real traffic uses.  A step (with or without
        an arrival) or a prefill runs
        on a cache of its own, and the warm-up compiles several shapes at
        once: as many of them hold a cache as the device's free memory
        takes (:meth:`_warm_cache_slots`).  The vocoder reads a row's units
        and nothing else of a cache, so it is handed nothing else."""
        if shape[0] == "vocode":
            shapes = jax.eval_shape(self.new_cache)
            units, _ = self.backbone.units_of(shapes, 1)
            cache = {k: jnp.zeros(v.shape, v.dtype) if v is units else v
                     for k, v in shapes.items()}
            jax.block_until_ready(self.vocode(cache, 0, 1, shape[1])[0])
            return
        from ..synth.steploop import DUMP_ROWS

        with self._warm_cache_slots():
            cache = self.new_cache()
            idle = (np.zeros((self.slots,), bool),
                    np.zeros((self.slots,), np.float32), 0)
            if shape[0] == "prefill":
                out = self.prefill(cache, 0,
                                   [0] * self._fewest_ids(shape[1]), 0.0)[:3]
            else:
                out = self.step(cache, *idle) if shape[0] == "step" else \
                    self.step_admit(cache, *idle, 0,
                                    [0] * self._fewest_ids(shape[1]), 0.0)[:4]
                # the gather of what flagged rows keep of a launch is a
                # program too (one for a step's logits, one for a carrying
                # step's, which hold a row more): cold, it compiled on the
                # loop's thread in front of the first flagged row's step
                # (PERF.md §7)
                out = (out, self.take_rows(out[1], [0] * DUMP_ROWS))
            jax.block_until_ready(out)
            del cache, out

    def _warm_cache_slots(self) -> threading.Semaphore:
        """How many warm-up dispatches may hold a cache at once: what the
        device says is free over a cache's bytes, less one for what a
        program reserves beside it; at least one, and no bound where the
        backend reports no memory (the CPU).  A cache of state that does not
        grow with a row is gigabytes at a few hundred slots: four of them
        beside the weights do not fit a chip, and where not even one fits
        beside the cache of a step loop that stands idle (the server speaks
        one utterance before it warms the lattice), that loop is let go
        first: the first row starts another."""
        with self._jit_lock:
            if self._warm_caches is None:
                need = max(1, sum(int(np.prod(a.shape)) * a.dtype.itemsize
                                  for a in jax.tree_util.tree_leaves(
                                      jax.eval_shape(self.new_cache))))
                free = self._free_bytes()
                if 0 < free < 2 * need and self._rest_idle_loop():
                    free = self._free_bytes()
                fit = max(1, free // need - 1) if free > 0 else 1 << 30
                self._warm_caches = threading.Semaphore(int(fit))
            return self._warm_caches

    @staticmethod
    def _free_bytes() -> int:
        """What the device says is free (0: it keeps no count)."""
        stats = jax.local_devices()[0].memory_stats() or {}
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    def _rest_idle_loop(self) -> bool:
        """Close the step loop if it holds no row (its cache goes with it);
        whether it did."""
        with self._loop_lock:
            loop = self._loop
            if loop is None or loop.slots.in_use:
                return False
            self._loop = None
        loop.close()
        return True

    # -- the step loop's engine ------------------------------------------------
    def new_cache(self) -> dict:
        return self.backbone.new_cache(self.slots, self.positions)

    def plan(self, n_ids: int, budget: int) -> RowPlan:
        """What a row of ``n_ids`` prompt ids and ``budget`` units will ask
        of the loop."""
        return self.backbone.plan(n_ids, budget)

    def _program(self, key: tuple, build):
        """The jitted program of ``key``, built once."""
        with self._jit_lock:
            fn = self._programs.get(key)
            if fn is None:
                fn = self._programs[key] = build()
        return fn

    def _build_vocode(self, frames: int):
        hp = self.hp

        def unit_vocode(generator, unit_table, units, slot, start, count):
            # a gather, not a dynamic_slice: a row's units may start behind
            # its prompt, and a slice of ``frames`` from there may run past
            # the row's end, where XLA would move the slice's start back
            at = jnp.minimum(start + jnp.arange(frames), units.shape[1] - 1)
            row = units[slot][at][None]
            valid = (jnp.arange(frames) < count)[None, :, None]
            z = jnp.where(valid, unit_table[row], 0.0)
            return decode_opts.decode_quantize(
                generator, hp, z, jnp.reshape(count, (1,)), None)

        return jax.jit(unit_vocode)

    def step(self, cache, live, temperature, step_no: int):
        """One launch over every slot.  Returns the cache, what a flagged
        row keeps of it (a tuple of arrays by slot) and the expert layers'
        load."""
        fn = self._program(("step",), self.backbone.build_step)
        return fn(self.params, cache, live, temperature, np.int32(step_no))

    def carries(self, n_ids: int) -> bool:
        """Whether a row of ``n_ids`` prompt ids is admitted in a step
        (:meth:`step_admit`) and not by a prefill apart: the backbone
        builds such a step, and the prompt's rows beside the slots' leave
        the expert products what a step alone runs them on (a shape the
        kernel's tile rule hands to ``ragged_dot`` would cost every live
        row its stream)."""
        if self.backbone.build_step_admit is None:
            return False
        t = bucket_for(n_ids, TEXT_BUCKETS)
        return expert_matmul(self.cfg, self.slots + t,
                                  self.backbone.held) == self.expert_matmul

    def _arrival(self, ids: list, rows: int) -> tuple:
        """A prompt as its program takes it (padded to its text bucket, its
        length, the row's number for its key) and what the row's
        ``prefill`` span says of the program: ``rows`` tokens go through
        the expert products beside the prompt's."""
        t = bucket_for(len(ids), TEXT_BUCKETS)
        padded = np.zeros((t,), np.int32)
        padded[:len(ids)] = ids
        shape = {"text_bucket": t,
                 "expert_matmul": expert_matmul(self.cfg, rows + t,
                                                     self.backbone.held),
                 # a prompt attends over itself, whole
                 "attention": "einsum"}
        shape.update(self.description.prefill(t))
        self._prefill_no += 1
        return padded, np.int32(len(ids)), np.int32(self._prefill_no), shape

    def prefill(self, cache, slot: int, ids: list, temperature: float):
        padded, n, row_no, shape = self._arrival(ids, 0)
        # one jitted function: a text bucket is a shape of its argument
        fn = self._program(("prefill",), self.backbone.build_prefill)
        # ``compile``: whether a backend compile or a load from the
        # persistent cache ran under this launch, on this thread
        with tracing.compile_sink() as paid:
            cache, out, load = fn(
                self.params, cache, padded, n, np.int32(slot),
                np.float32(temperature), row_no)
        shape.update(tracing.launch_compile(paid))
        return cache, out, load, shape

    def step_admit(self, cache, live, temperature, step_no: int, slot: int,
                   ids: list, row_temperature: float):
        """One launch over every slot that carries the row arriving in
        ``slot`` (not live in it): :meth:`step` and :meth:`prefill` in one
        program.  Returns the cache, what a flagged row keeps of the step,
        the load of both together, what a flagged arrival keeps of its
        prompt, and what the row's ``prefill`` span says of the program."""
        padded, n, row_no, shape = self._arrival(ids, self.slots)
        fn = self._program(("step_admit",), self.backbone.build_step_admit)
        with tracing.compile_sink() as paid:
            cache, kept, first, load = fn(
                self.params, cache, live, temperature, np.int32(step_no),
                padded, n, np.int32(slot), np.float32(row_temperature),
                row_no)
        shape.update(tracing.launch_compile(paid))
        return cache, kept, load, first, shape

    def vocode(self, cache, slot: int, n_ids: int, units: int):
        """The vocoder program of one retired row, enqueued: the slot's
        units through the unit table and the generator, at the row's
        frame bucket."""
        f = min(bucket_for(units, FRAME_BUCKETS), self.positions)
        fn = self._program(("vocode", f), lambda: self._build_vocode(f))
        held, start = self.backbone.units_of(cache, n_ids)
        with tracing.compile_sink() as paid:
            out = fn(self.generator, self.unit_table, held, np.int32(slot),
                     np.int32(start), np.int32(units))
        prefetch_to_host(out)
        return out, {"batch_bucket": 1, "frames_bucket": f,
                     **tracing.launch_compile(paid)}

    def wait_audio(self, out) -> None:
        """Block until the vocoder program of ``out`` has run."""
        jax.block_until_ready(out)

    def fetch_audio(self, out, units: int) -> np.ndarray:
        """The row's samples, float32 at the generator's own amplitudes
        (the int16 on the wire is the peak-scaled device epilogue's)."""
        wav_i16, wav_lengths, peak = jax.device_get(out)
        peak = max(float(peak[0]), 0.01)
        return wav_i16[0, :int(wav_lengths[0])].astype(np.float32) * (
            peak / 32767.0)

    def dumped(self, plan: RowPlan, done: int) -> bool:
        """Whether a flagged row keeps what its launch number ``done``
        gave: the dump thins by the backbone's rule."""
        return self.backbone.dumped(plan, done)

    def row_record(self, cache, slot: int):
        out = self.backbone.record(cache, slot)
        for a in out:
            a.copy_to_host_async()
        return out

    def take_rows(self, kept: tuple, rows: list):
        """The slots ``rows`` of what a launch gave (:meth:`step`)."""
        fn = self._program(("take",), lambda: jax.jit(self.backbone.take))
        return fn(kept, np.asarray(rows, np.int32))

    def dump(self, ids: list, budget: int, kept: list, record) -> dict:
        """What the timed path produced for a flagged row, as arrays: the
        prompt, what the backbone recorded of the row, and what the kept
        launches gave (``kept``: ``(launch, arrays)``, -1 the prefill)."""
        record = tuple(np.asarray(a) for a in record)
        return dict(self.backbone.dump(ids, budget, kept, record),
                    ids=np.asarray(ids, np.int32))
