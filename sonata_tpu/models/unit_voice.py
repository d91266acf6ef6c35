"""A unit voice: an autoregressive acoustic front over a HiFi-GAN vocoder.

The sentence's phoneme ids are the prompt; an LFM2-MoE backbone
(:mod:`.lfm2`) decodes one acoustic-unit id a frame through its
key-value cache and convolution state; a unit table maps ids to the
generator's latent; and the HiFi-GAN generator a Piper voice runs
(:func:`.vits.decode`, time-folded stages and all) turns frames into
samples, through the same on-device int16 epilogue
(:func:`.decode_opts.decode_quantize`).

The voice JSON says so with ``"family": "unit_lm"``
(:func:`sonata_tpu.models.from_config_path`); beside Piper's keys
(``audio``, ``espeak``, ``inference``, ``phoneme_id_map``, ``model`` with
the generator's sizes) it holds ``backbone`` (the LFM2 ``config.json``) and
``units``: ``first_id`` (ids below it are phoneme ids, the others units),
``stop_id`` and ``frames_per_id``.

**The length rule.**  A row decodes exactly ``round(frames_per_id *
length_scale * ids)`` units: the stop unit is compared every step
(``cache["stops"]``) but suppressed below that count and forced at it.
Sampling is temperature ``noise_scale`` over the unit ids (0: greedy).

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
from . import decode_opts, lfm2
from .config import ModelConfig, SynthesisConfig
from .serialization import load_params, unflatten_params

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


def load_weights(directory: Path, cfg: lfm2.Lfm2Config) -> dict:
    """A voice directory's weights, one layer at a time."""
    tensors = directory / "tensors"

    def layer(i: int) -> dict:
        prefix = f"layers.{i}."
        names = sorted(p.name[len(prefix):].rsplit(".", 2)[0]
                       for p in tensors.glob(prefix + "*.npy"))
        if not names:
            raise FailedToLoadResource(f"no tensors of layer {i} under "
                                       f"{tensors}")
        return lfm2.pack_layer(unflatten_params(
            {name.replace(".", "/"): load_tensor(tensors, prefix + name)
             for name in names}))

    generator = directory / "generator.npz"
    if not generator.exists():
        raise FailedToLoadResource(f"no generator weights at {generator}")
    return {
        "backbone": {
            "embed": load_tensor(tensors, "embed"),
            "norm_f": load_tensor(tensors, "norm_f").astype(jnp.float32),
            "layers": [layer(i) for i in range(len(cfg.layer_types))]},
        "unit_table": load_tensor(tensors, "unit_table"),
        "generator": jax.device_put(load_params(generator))}


class UnitVoice(BaseModel):
    """A loaded unit voice: config, weights on the device, compiled
    programs and the step loop."""

    def __init__(self, config: ModelConfig, backbone: dict, units: dict,
                 weights: dict, *, seed: int = 0):
        self.config = config
        self.hp = config.hyper
        self.cfg = lfm2.Lfm2Config.from_dict(backbone)
        self.units = lfm2.UnitIds(int(units["first_id"]),
                                  int(units["stop_id"]))
        self.frames_per_id = float(units["frames_per_id"])
        if not 0 < self.units.first_id <= self.units.stop_id \
                < self.cfg.vocab_size:
            raise OperationError(f"units {units} do not split a vocabulary "
                                 f"of {self.cfg.vocab_size}")
        # the two sizes every program's shape hangs on: the operator's,
        # read once, here
        self.slots = int(os.environ.get(SLOTS_ENV) or DEFAULT_SLOTS)
        self.positions = int(os.environ.get(POSITIONS_ENV)
                             or DEFAULT_POSITIONS)
        self.expert_layers = self.cfg.expert_layers
        self.params = weights["backbone"]
        self.unit_table = weights["unit_table"]
        self.generator = {"dec": weights["generator"]["dec"]}
        if self.unit_table.shape != (self.cfg.vocab_size,
                                     self.hp.inter_channels):
            raise OperationError(
                f"unit table {self.unit_table.shape} is not vocabulary x "
                f"latent ({self.cfg.vocab_size}, {self.hp.inter_channels})")
        self._seed = int(seed)
        self._synth_lock = threading.Lock()
        self._synth_config = config.inference.copy()
        self._jit_lock = threading.Lock()
        self._programs: dict = {}
        self._used: set = set()
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
            cfg = lfm2.Lfm2Config.from_dict(backbone)
        except (KeyError, ValueError) as e:
            raise FailedToLoadResource(
                f"{path} is not a unit voice: {type(e).__name__}: {e}") from e
        with _PLACED_LOCK:
            weights = _PLACED.pop(str(path.resolve()), None)
        if weights is None:
            weights = load_weights(path.parent, cfg)
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
            if len(ids) + budget - 1 > self.positions:
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
        """Every program a request can need: the step, a prefill for each
        text bucket a slot can hold with its frames, a vocoder for each
        frame bucket those give (``minimal``: the step alone; the rest
        compiles on first use).  The whole list, and not what some traffic
        happens to reach, because a first use compiles on the loop's own
        thread: every live row stands still for as long as it takes.  What
        fits a slot bounds it: at 1024 positions the step, 7 prefills and
        7 vocoders."""
        shapes = [("step",)]
        if mode == "full":
            texts = [t for t in TEXT_BUCKETS
                     if self._fits(self._fewest_ids(t))]
            longest = max(n for n in range(1, self.positions)
                          if self._fits(n))
            frames = sorted({bucket_for(self.frame_budget(n), FRAME_BUCKETS)
                             for n in range(1, longest + 1)})
            shapes += [("prefill", t) for t in texts]
            shapes += [("vocode", f) for f in frames]
        return shapes

    def _fewest_ids(self, bucket: int) -> int:
        below = [t for t in TEXT_BUCKETS if t < bucket]
        return (below[-1] if below else 0) + 1

    def _fits(self, n_ids: int) -> bool:
        return n_ids + self.frame_budget(n_ids) - 1 <= self.positions

    def warm_shape(self, shape: tuple) -> None:
        """Compile one program of :meth:`lattice_shapes`: a dummy dispatch
        on a cache of its own through the jit cache real traffic uses."""
        cache = self.new_cache()
        if shape[0] == "step":
            out = self.step(cache, np.zeros((self.slots,), bool),
                            np.zeros((self.slots,), np.float32), 0)
        elif shape[0] == "prefill":
            out = self.prefill(cache, 0, [0] * self._fewest_ids(shape[1]),
                               0.0)[:3]
        else:
            out = self.vocode(cache, 0, shape[1])[0]
        jax.block_until_ready(out)

    # -- the step loop's engine ------------------------------------------------
    def new_cache(self) -> dict:
        return lfm2.new_cache(self.cfg, self.slots, self.positions)

    def _program(self, key: tuple, build):
        """The jitted program of ``key``, built once."""
        with self._jit_lock:
            fn = self._programs.get(key)
            if fn is None:
                fn = self._programs[key] = build()
        return fn

    def _first_use(self, shape: tuple) -> str:
        """``cold`` the first time a program runs at ``shape`` (it
        compiles, or loads from the persistent cache), else ``cached``."""
        with self._jit_lock:
            seen = shape in self._used
            self._used.add(shape)
        return "cached" if seen else "cold"

    def _build_step(self):
        cfg, units, seed = self.cfg, self.units, self._seed

        def lfm2_step(params, cache, live, temperature, step_no):
            return lfm2.step(params, cache, live, temperature, step_no,
                             cfg=cfg, units=units, seed=seed)

        return jax.jit(lfm2_step, donate_argnums=(1,))

    def _build_prefill(self):
        cfg, units, seed = self.cfg, self.units, self._seed

        def lfm2_prefill(params, cache, ids, n, slot, temperature, row_no):
            key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), row_no)
            return lfm2.prefill(params, cache, ids, n, slot, temperature,
                                key, cfg=cfg, units=units)

        return jax.jit(lfm2_prefill, donate_argnums=(1,))

    def _build_vocode(self, frames: int):
        hp = self.hp

        def unit_vocode(generator, unit_table, units, slot, count):
            row = jax.lax.dynamic_slice(units, (slot, 0), (1, frames))
            valid = (jnp.arange(frames) < count)[None, :, None]
            z = jnp.where(valid, unit_table[row], 0.0)
            return decode_opts.decode_quantize(
                generator, hp, z, jnp.reshape(count, (1,)), None)

        return jax.jit(unit_vocode)

    def step(self, cache, live, temperature, step_no: int):
        fn = self._program(("step",), self._build_step)
        return fn(self.params, cache, live, temperature, np.int32(step_no))

    def prefill(self, cache, slot: int, ids: list, temperature: float):
        t = bucket_for(len(ids), TEXT_BUCKETS)
        padded = np.zeros((t,), np.int32)
        padded[:len(ids)] = ids
        # one jitted function: a text bucket is a shape of its argument
        fn = self._program(("prefill",), self._build_prefill)
        shape = {"text_bucket": t,
                 "compile": self._first_use(("prefill", t))}
        self._prefill_no += 1
        cache, logits, load = fn(
            self.params, cache, padded, np.int32(len(ids)), np.int32(slot),
            np.float32(temperature), np.int32(self._prefill_no))
        return cache, logits, load, shape

    def vocode(self, cache, slot: int, units: int):
        """The vocoder program of one retired row, enqueued: the slot's
        units through the unit table and the generator, at the row's
        frame bucket."""
        f = min(bucket_for(units, FRAME_BUCKETS), self.positions)
        fn = self._program(("vocode", f), lambda: self._build_vocode(f))
        out = fn(self.generator, self.unit_table, cache["units"],
                 np.int32(slot), np.int32(units))
        prefetch_to_host(out)
        return out, {"batch_bucket": 1, "frames_bucket": f,
                     "compile": self._first_use(("vocode", f))}

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

    def row_record(self, cache, slot: int):
        out = (cache["units"][slot], cache["routes"][slot])
        for a in out:
            a.copy_to_host_async()
        return out

    def take_rows(self, logits, rows: list):
        fn = self._program(("take",), lambda: jax.jit(
            lambda logits, rows: logits[rows]))
        return fn(logits, np.asarray(rows, np.int32))
