#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the real serving path starts,
compiles and answers on the chip.

    python3 chip_smoke.py                  # one TPU chip, the defaults
    python3 chip_smoke.py --replicas 4     # four chips, replica pool
    python3 chip_smoke.py --mesh-devices 4 # four chips, one data mesh
    python3 chip_smoke.py --rehearse       # tiny voice on the CPU

What it does, in sequential child processes (this process never imports
jax — a parent that has touched JAX holds the chip, and a child that needs
it then fails or hangs):

1. *voice writer* (CPU-pinned; it only initialises weights): a voice at
   every published lessac-high width (hidden 192, filter 768, HiFi-GAN
   512→[8,8,2,2] with resblock kernels 3/7/11, 22.05 kHz) from a seed, as
   ``voice.onnx.json`` + ``voice.npz``.  Depth is cut (``DEPTH_CUT``): at
   full depth one cold compile of a full-pipeline shape takes 23–74 s on a
   v5e, and even the ``minimal`` lattice would not fit this script's time
   limit (PERF.md, "Where the time goes");
2. *server* (holds the chip): ``python -m sonata_tpu.frontends.grpc_server
   --voice … --continuous-batching --metrics-port …`` — the documented
   deployment command.  This process is the gRPC client: batched,
   realtime and eight concurrent realtime requests over the wire, every
   response checked, then ``/metrics``, ``/debug/traces``, a SIGTERM drain
   and a scan of the server log for anything caught and forgotten.

Every child runs with ``JAX_PLATFORMS=tpu``, so a missing chip is JAX's own
start-up error and never a CPU run.  ``--rehearse`` is the only way off the
chip: the same phases with a tiny voice and ``JAX_PLATFORMS=cpu``, for
debugging the command before chip time is spent.  Any failed check exits
non-zero; the last line of standard output is ``{"ok": true, "device":
{...}}`` only when every phase passed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import types
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT_DIR = REPO / "chiprun_out" / "chip_smoke"
SEED = 20260926
#: the contract's limit for one run, compilation included
TIME_LIMIT_S = 1200.0
#: the server's own default, set explicitly so the output can name it
WARMUP_BUDGET_S = 600.0

#: user-set A/B arms of the hot path (ROADMAP D1): the smoke proves the
#: default path, so it refuses to run with any of them in the environment
ARM_KNOBS = ("SONATA_BATCH_MODE", "SONATA_ITER_PIPELINE",
             "SONATA_FUSED_EPILOGUE", "SONATA_DECODE_QUANT",
             "SONATA_COMPUTE_DTYPE", "SONATA_TCONV",
             "SONATA_DISPATCH_POLICY")

#: depth cut of the lessac-high voice, no width touched: text-encoder
#: layers 6→2, flow coupling layers 4→2 (one flip pair), WaveNet layers
#: per coupling 4→2, duration-predictor flows 4→2, resblock dilation stack
#: (1, 3, 5)→(1,) under each of the kernels 3/7/11.  28.4 M → 11.4 M
#: parameters; a cold full-pipeline compile drops about 3x (PERF.md)
DEPTH_CUT = dict(n_layers=2, flow_n_layers=2, flow_wn_layers=2,
                 dp_n_flows=2, resblock_dilation_sizes=[[1], [1], [1]])

#: the structurally complete tiny voice of the CPU tests (rehearsal only)
TINY_MODEL = dict(
    inter_channels=32, hidden_channels=32, filter_channels=64, n_heads=2,
    n_layers=2, upsample_rates=[4, 4], upsample_initial_channel=64,
    upsample_kernel_sizes=[8, 8], resblock_kernel_sizes=[3],
    resblock_dilation_sizes=[[1, 3]], dp_filter_channels=32,
    gin_channels=16, flow_n_layers=2, flow_wn_layers=2)

SENTENCES = (
    "The quick brown fox jumps over the lazy dog near the river bank.",
    "Speech synthesis turns written language into audible sound waves.",
    "Modern accelerators compile the whole network into one program.",
    "Each sentence becomes a batch row padded to a fixed bucket length.",
    "The decoder upsamples latent frames into waveform samples.",
    "Streaming mode trades throughput for time to first byte.",
    "Benchmarks should measure steady state after warmup compilation.",
    "Large batches amortize dispatch latency across many sentences.",
    "A narrator reads one sentence while the next is already queued.",
    "Quantized samples travel back to the host as compact integers.",
    "Every audio frame expands into two hundred fifty six samples.",
    "The encoder walks the phoneme sequence with windowed attention.",
    "A normalizing flow turns simple noise into rich acoustic detail.",
    "The duration predictor decides how long each phoneme should last.",
    "Parallel chips can each synthesize their own slice of the batch.",
    "This paragraph has exactly sixteen sentences for the batch.",
)
SHORT = "Hello there."
#: one-window utterances for the eight concurrent streams
SHORTS = ("Hello there.", "Good morning.", "Thank you all.", "See you soon.",
          "Well done.", "Come on in.", "Not so fast.", "All is well.")
MEDIUM = " ".join(SENTENCES[:3])
PARAGRAPH = " ".join(SENTENCES)
LONG_SENTENCE = (
    "A longer sentence exercises the larger text and frame buckets, so "
    "that a stream with many chunks joins the running batch, rides "
    "several iterations beside its neighbours, and retires at an "
    "iteration boundary when its last window has been decoded.")

#: log lines (regexes) that mean something was caught and forgotten
FORBIDDEN_LOG = (r"AOT warm of .* failed", r"dispatch probe failed",
                 r"native build of \S+ failed", r"WarmupBudgetExceeded",
                 r"warmup budget expired", r"Traceback \(most recent call")
T0 = time.monotonic()


def elapsed() -> float:
    return time.monotonic() - T0


def say(msg: str) -> None:
    print(f"[{elapsed():7.1f}s] {msg}", flush=True)


def fail(msg: str) -> "NoReturn":  # noqa: F821
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# ---------------------------------------------------------------------------
# child phases (each is its own process and the only holder of the chip)
# ---------------------------------------------------------------------------

def child_write_voice(out_dir: str, tiny: bool) -> dict:
    """Seeded random weights at the published lessac-high widths, depth
    cut (or the tiny test geometry), in the on-disk format the server
    loads."""
    from sonata_tpu.models import PiperVoice
    from sonata_tpu.models.config import (
        ModelConfig,
        default_phoneme_id_map,
    )
    from sonata_tpu.models.serialization import save_params

    id_map = default_phoneme_id_map()
    cfg = {"audio": ({"sample_rate": 16000, "quality": None} if tiny
                     else {"sample_rate": 22050, "quality": "high"}),
           "num_speakers": 1, "speaker_id_map": {},
           "espeak": {"voice": "en-us"},
           "num_symbols": len(id_map), "phoneme_id_map": id_map}
    cfg["model"] = TINY_MODEL if tiny else DEPTH_CUT
    voice = PiperVoice.random(ModelConfig.from_dict(cfg), seed=SEED)
    out = Path(out_dir)
    config_path = out / "voice.onnx.json"
    config_path.write_text(json.dumps(cfg))
    save_params(out / "voice.npz", voice.params)
    import jax

    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        voice.params))
    return {"config_path": str(config_path), "parameters": n_params,
            "model_overrides": cfg["model"],
            "hidden_channels": voice.hp.hidden_channels,
            "filter_channels": voice.hp.filter_channels,
            "upsample_rates": list(voice.hp.upsample_rates),
            "upsample_initial_channel": voice.hp.upsample_initial_channel}


def _device_facts() -> dict:
    import jax

    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "jax": jax.__version__,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def child_mesh_check(config_path: str, n_devices: int) -> dict:
    """One full-pipeline dispatch of the voice on an ``n_devices`` data
    mesh: outputs sharded over distinct devices, the same audio as the
    unsharded program."""
    import jax
    import numpy as np

    from sonata_tpu.models import from_config_path
    from sonata_tpu.parallel import make_mesh
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    facts = _device_facts()
    b, t, f = 2 * n_devices, 64, 256
    meshed = from_config_path(config_path, mesh=make_mesh(n_devices))
    wav, lengths, _peaks, _frames = meshed._full_fn(b, t, f)(
        *meshed._dummy_full_args(b, t))
    devices = sorted(str(s.device) for s in wav.addressable_shards)
    if len(set(devices)) != n_devices:
        raise SystemExit(f"mesh: output lives on {devices}, expected "
                         f"{n_devices} distinct devices")
    single = from_config_path(config_path)
    wav1, lengths1, _p, _f = single._full_fn(b, t, f)(
        *single._dummy_full_args(b, t))
    wav, wav1 = np.asarray(wav, np.int32), np.asarray(wav1, np.int32)
    if not np.array_equal(np.asarray(lengths), np.asarray(lengths1)):
        raise SystemExit("mesh: sample lengths differ from one device")
    # peak-scaled int16: float reassociation across the partitioner may
    # move a sample by a few steps of the grid, never by a percent of it
    worst = int(np.max(np.abs(wav - wav1)))
    if worst > 327:
        raise SystemExit(f"mesh: audio differs from one device by "
                         f"{worst} int16 steps")
    return {"device": facts, "shape": [b, t, f],
            "output_devices": devices,
            "max_abs_diff_int16_vs_one_device": worst}


def run_child_phase(argv: list) -> int:
    phase, rest = argv[0], argv[1:]
    if phase == "write-voice":
        result = child_write_voice(rest[0], tiny=rest[1] == "tiny")
    elif phase == "mesh-check":
        result = child_mesh_check(rest[0], int(rest[1]))
    else:
        raise SystemExit(f"unknown phase {phase!r}")
    print("RESULT " + json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# parent: spawning
# ---------------------------------------------------------------------------

def child_env(platform: str, **extra: str) -> dict:
    return dict(os.environ, JAX_PLATFORMS=platform, **extra)


def spawn_phase(phase: str, args: list, env: dict, timeout_s: float) -> dict:
    """Run one child phase to its end; its last RESULT line is the
    phase's record.  A non-zero exit fails the run."""
    check("jax" not in sys.modules, "the parent process imported jax")
    say(f"phase {phase}: start (JAX_PLATFORMS={env['JAX_PLATFORMS']})")
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--phase", phase,
         *args], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout_s)
    (OUT_DIR / f"phase_{phase}.log").write_text(
        proc.stdout + "\n--- stderr ---\n" + proc.stderr)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        fail(f"phase {phase} exited {proc.returncode}")
    results = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    check(bool(results), f"phase {phase} printed no RESULT line")
    return json.loads(results[-1][len("RESULT "):])


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, timeout_s: float = 10.0) -> tuple:
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as resp:
            return resp.getcode(), resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def cache_entries(cache_dir: Path) -> int:
    if not cache_dir.is_dir():
        return 0
    return sum(1 for p in cache_dir.rglob("*") if p.is_file())


def import_without_jax(module: str):
    """One of the repo's own jax-free modules, loaded without running the
    package ``__init__`` files above it (``sonata_tpu/__init__.py`` imports
    jax): stub parent packages that only carry a ``__path__``, then a
    normal submodule import."""
    parts = module.split(".")
    for i in range(1, len(parts)):
        pkg = ".".join(parts[:i])
        if pkg not in sys.modules:
            stub = types.ModuleType(pkg)
            stub.__path__ = [str(REPO.joinpath(*parts[:i]))]
            sys.modules[pkg] = stub
    loaded = importlib.import_module(module)
    check("jax" not in sys.modules, f"importing {module} pulled jax in")
    return loaded


# ---------------------------------------------------------------------------
# parent: the server under test
# ---------------------------------------------------------------------------

class Server:
    """One boot of ``python -m sonata_tpu.frontends.grpc_server``."""

    def __init__(self, config_path: str, platform: str, lattice: str,
                 extra_args: list, extra_env: dict, name: str):
        self.grpc_port, self.metrics_port = free_port(), free_port()
        self.log_path = OUT_DIR / f"server_{name}.log"
        self.base = f"http://127.0.0.1:{self.metrics_port}"
        self.cmd = [sys.executable, "-m", "sonata_tpu.frontends.grpc_server",
                    "--voice", config_path, "--continuous-batching",
                    "--port", str(self.grpc_port),
                    "--metrics-port", str(self.metrics_port), *extra_args]
        env = child_env(platform, SONATA_WARMUP_LATTICE=lattice,
                        SONATA_WARMUP_BUDGET_S=str(WARMUP_BUDGET_S),
                        **extra_env)
        check("jax" not in sys.modules, "the parent process imported jax")
        say("server: " + " ".join(self.cmd[1:]))
        self._log = open(self.log_path, "w")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(self.cmd, env=env, cwd=REPO,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def wait_ready(self, deadline_s: float) -> float:
        """Seconds from spawn to the first 200 on /readyz; the warmup
        gauge is printed on the way, as the compile-rate curve."""
        next_report = 30.0
        while True:
            if self.proc.poll() is not None:
                sys.stderr.write(self.log_text()[-6000:])
                fail(f"server exited {self.proc.returncode} before ready")
            waited = time.monotonic() - self.t_spawn
            try:
                if http_get(self.base + "/readyz", 30.0)[0] == 200:
                    return time.monotonic() - self.t_spawn
                if waited >= next_report:
                    next_report += 30.0
                    say(f"  warming: sonata_warmup_progress = "
                        f"{self.metrics().get('sonata_warmup_progress')}")
            except OSError:
                pass  # the metrics plane is not listening yet
            if waited > deadline_s:
                sys.stderr.write(self.log_text()[-6000:])
                fail(f"server not ready after {deadline_s:.0f} s")
            time.sleep(1.0)

    def metrics(self) -> dict:
        """{series-with-labels: value} from /metrics."""
        code, body = http_get(self.base + "/metrics")
        check(code == 200, f"/metrics answered {code}")
        out = {}
        for line in body.splitlines():
            m = re.match(r"^(\w+(?:\{[^}]*\})?)\s+(\S+)$", line)
            if m and not line.startswith("#"):
                out[m.group(1)] = float(m.group(2))
        return out

    def trace(self, request_id: str) -> dict:
        code, body = http_get(f"{self.base}/debug/traces?id={request_id}")
        check(code == 200, f"/debug/traces answered {code}")
        traces = json.loads(body)["traces"]
        check(len(traces) == 1, f"{len(traces)} traces for {request_id}")
        return traces[0]

    def sigterm_and_wait(self, timeout_s: float = 120.0) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            fail(f"server still running {timeout_s:.0f} s after SIGTERM")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def series_sum(metrics: dict, name: str) -> float:
    return sum(v for k, v in metrics.items()
               if k == name or k.startswith(name + "{"))


def parse_boot_log(text: str) -> dict:
    """What the server resolved at start-up, as its own log says it."""
    facts: dict = {}
    m = re.search(r"devices: platform=(\S+) device_kind=(.+?) count=(\d+)",
                  text)
    check(m is not None, "no 'devices:' line in the server log")
    facts["device"] = {"platform": m.group(1), "kind": m.group(2).strip(),
                       "count": int(m.group(3))}
    m = re.search(r"(dispatch policy \[\S+\]: coalesce=(on|off) .*? via "
                  r"(\S+)( probe\(.*?\))?); batch mode=(\w+)", text)
    check(m is not None, "no dispatch-policy line in the server log")
    facts["policy"] = {"line": m.group(1), "coalesce": m.group(2) == "on",
                       "source": m.group(3),
                       "probe": (m.group(4) or "").strip() or None}
    facts["batch_mode"] = m.group(5)
    m = re.search(r"persistent compile cache: (\S+)", text)
    check(m is not None, "no compile-cache line in the server log")
    facts["cache_dir"] = m.group(1)
    facts["lattice_shapes_warmed"] = sum(
        int(n) for n in re.findall(
            r"warmup lattice \S+: (\d+) shape\(s\) warm", text))
    m = re.search(r"readiness: .* \(warmup lattice mode=(\w+), (\{.*\})\)",
                  text)
    check(m is not None, "no readiness line in the server log")
    facts["lattice_mode"] = m.group(1)
    facts["warmup_progress"] = m.group(2)
    return facts


# ---------------------------------------------------------------------------
# parent: the gRPC client
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, pb, port: int, config_path: str):
        import grpc

        self.pb = pb
        self.channel = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_receive_message_length", 64 << 20)])
        self._seq = 0
        self._lock = threading.Lock()
        # LoadVoice is idempotent per path: it names the preloaded voice
        info = self._stub("LoadVoice", pb.VoiceInfo, False)(
            pb.VoicePath(config_path=config_path), timeout=60.0)
        self.voice_id = info.voice_id
        self.sample_rate = info.audio.sample_rate

    def _stub(self, method: str, response, stream: bool):
        factory = (self.channel.unary_stream if stream
                   else self.channel.unary_unary)
        return factory(f"/sonata_grpc.sonata_grpc/{method}",
                       request_serializer=lambda m: m.encode(),
                       response_deserializer=response.decode)

    def synthesize(self, kind: str, request, label: str) -> dict:
        """One streamed RPC, every message checked: non-empty, whole
        int16 samples, not all zero, finite rtf where one is reported."""
        with self._lock:
            self._seq += 1
            rid = f"smoke-{label}-{self._seq}"
        method, response = {
            "batched": ("SynthesizeUtterance", self.pb.SynthesisResult),
            "realtime": ("SynthesizeUtteranceRealtime",
                         self.pb.WaveSamples)}[kind]
        t0 = time.monotonic()
        ttfb, samples, messages = None, 0, 0
        for msg in self._stub(method, response, True)(
                request, timeout=300.0,
                metadata=(("x-request-id", rid),)):
            if ttfb is None:
                ttfb = time.monotonic() - t0
            raw = msg.wav_samples
            check(len(raw) > 0 and len(raw) % 2 == 0,
                  f"{rid}: message of {len(raw)} bytes")
            check(any(raw), f"{rid}: an all-zero message")
            if kind == "batched":
                check(math.isfinite(msg.rtf) and msg.rtf > 0.0,
                      f"{rid}: rtf {msg.rtf}")
            samples += len(raw) // 2
            messages += 1
        check(messages > 0, f"{rid}: no audio came back")
        return {"request_id": rid, "kind": kind, "label": label,
                "chars": len(request.text), "messages": messages,
                "samples": samples,
                "audio_s": round(samples / self.sample_rate, 3),
                "ttfb_s": round(ttfb, 3),
                "wall_s": round(time.monotonic() - t0, 3)}


def drive_traffic(server: Server, client: Client, policy_coalesces: bool,
                  batch_mode: str, lattice: str) -> dict:
    pb, voice_id = client.pb, client.voice_id
    before = server.metrics()

    def utterance(text: str, **kw):
        return pb.Utterance(voice_id=voice_id, text=text, **kw)

    rows = []
    # batched RPC: short, medium, paragraph — through the scheduler
    for label, text in (("short", SHORT), ("medium", MEDIUM),
                        ("paragraph", PARAGRAPH)):
        rows.append(client.synthesize("batched", utterance(
            text, synthesis_mode=pb.SynthesisMode.BATCHED), label))
    batched = list(rows)
    # one request with speech args: the prosody DSP (native library built
    # from src/*.cpp on first use, or the numpy implementation)
    rows.append(client.synthesize("batched", utterance(
        SENTENCES[0], speech_args=pb.SpeechArgs(rate=20)), "prosody"))
    # realtime RPC at the default chunk 55 / padding 3 (fields left unset)
    for label, text in (("rt-short", SHORT), ("rt-medium", SENTENCES[1]),
                        ("rt-long", LONG_SENTENCE)):
        rows.append(client.synthesize("realtime", utterance(text), label))
    # eight concurrent realtime streams: the coalesced stage path and the
    # decode engine at batch > 1
    gate = threading.Barrier(8)
    results: list = [None] * 8

    def stream(i: int) -> None:
        gate.wait(timeout=60.0)
        try:
            results[i] = client.synthesize(
                "realtime", utterance(SHORTS[i]), f"rt-c{i}")
        except BaseException as e:  # re-raised in the parent thread below
            results[i] = e

    threads = [threading.Thread(target=stream, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=900.0)
        check(not th.is_alive(), "a concurrent stream never finished")
    for r in results:
        if isinstance(r, BaseException):
            raise r
    rows.extend(results)
    for r in rows:
        say("  {label:<10} {kind:<8} msgs={messages:<3} audio={audio_s:>7}s "
            "ttfb={ttfb_s:>7}s wall={wall_s:>7}s".format(**r))

    after = server.metrics()
    # sample counts against what the server reports it synthesized
    lbl = "{voice=\"%s\"}" % voice_id
    d_utt = (after[f"sonata_voice_utterances{lbl}"]
             - before.get(f"sonata_voice_utterances{lbl}", 0.0))
    d_ms = (after[f"sonata_voice_audio_ms{lbl}"]
            - before.get(f"sonata_voice_audio_ms{lbl}", 0.0))
    unary = [r for r in rows if r["kind"] == "batched"]
    n_results = sum(r["messages"] for r in unary)
    sent_ms = sum(r["samples"] for r in unary) * 1000.0 / client.sample_rate
    check(batched[2]["messages"] == len(SENTENCES),
          f"paragraph came back as {batched[2]['messages']} results")
    check(d_utt == n_results, f"server counted {d_utt} utterances, "
                              f"client received {n_results}")
    check(abs(d_ms - sent_ms) < 1.0,
          f"server reports {d_ms:.1f} ms of audio, the responses carried "
          f"{sent_ms:.1f} ms")

    # traces: realtime chunk counts, dispatch spans in the resolved mode
    # (iteration mode records one shared "dispatch" span per iteration,
    # mode=iteration; dispatch mode records per-stream decode-window spans)
    modes, max_rows, cold = set(), 0, {}
    for r in rows:
        spans = server.trace(r["request_id"])["spans"]
        names = {s["name"] for s in spans}
        wanted = ("decode-window" if r["kind"] == "realtime"
                  and batch_mode == "dispatch" else "dispatch")
        check(wanted in names,
              f"{r['request_id']}: no {wanted} span ({sorted(names)})")
        for s in spans:
            attrs = s.get("attrs") or {}
            if s["name"] == "stream-emit" and r["kind"] == "realtime":
                check(attrs.get("chunks") == r["messages"],
                      f"{r['request_id']}: server emitted "
                      f"{attrs.get('chunks')} chunks, client received "
                      f"{r['messages']}")
            if s["name"] == "dispatch" and attrs.get("compile") == "cold":
                cold[attrs.get("dispatch_id", s["span_id"])] = {
                    k: attrs.get(k) for k in (
                        "mode", "batch_bucket", "text_bucket",
                        "frame_bucket")}
            if s["name"] == "dispatch" and r["kind"] == "realtime":
                modes.add(attrs.get("mode"))
                if r["label"].startswith("rt-c"):
                    max_rows = max(max_rows, int(attrs.get("rows", 1)))
    check(modes == ({"iteration"} if batch_mode == "iteration" else set()),
          f"realtime dispatch spans carry mode(s) {sorted(modes)}, the "
          f"server resolved batch mode {batch_mode}")
    stage_req = series_sum(after, "sonata_stream_stage_requests")
    stage_disp = series_sum(after, "sonata_stream_stage_dispatches")
    if policy_coalesces:
        check(max_rows >= 2, "eight concurrent streams never shared a "
                             "decode dispatch (max rows 1)")
        check(stage_disp < stage_req,
              f"stream starts never coalesced ({stage_req:.0f} requests "
              f"in {stage_disp:.0f} dispatches)")

    # zero errors, zero refusals, and no cold compile after readiness
    # where the lattice mode promised coverage: `full` promises every
    # shape this traffic dispatches; `minimal` is batch 1 only, so a cold
    # dispatch at a batch bucket > 1 is that mode's documented gap and is
    # reported, not failed
    for name in ("sonata_request_failures_total", "sonata_shed_total",
                 "sonata_deadline_expired_total"):
        check(series_sum(after, name) == 0.0,
              f"{name} = {series_sum(after, name)} after the traffic")
    n_cold = series_sum(after, "sonata_runtime_cold_compiles_total")
    uncovered = [c for c in cold.values()
                 if lattice == "full" or (c["batch_bucket"] or 1) <= 1]
    if lattice != "off":  # off arms no containment: nothing was promised
        check(not uncovered, f"cold compiles after readiness inside the "
                             f"{lattice} lattice's promise: {uncovered}")
        check(n_cold == 0 or lattice == "minimal",
              f"sonata_runtime_cold_compiles_total = {n_cold}")
    check(series_sum(after, "sonata_warmup_progress") == 1.0,
          "sonata_warmup_progress is not 1")
    peaks = {k: v for k, v in after.items()
             if k.startswith("sonata_device_memory_peak_bytes")}
    return {"requests": rows, "metrics_before": before,
            "metrics_after": after,
            "runtime_cold_compiles_total": n_cold,
            "cold_dispatches_after_readiness": list(cold.values()),
            "realtime_dispatch_modes": sorted(modes),
            "max_rows_in_a_concurrent_decode_dispatch": max_rows,
            "stream_stage_requests": stage_req,
            "stream_stage_dispatches": stage_disp,
            "peak_bytes_in_use": peaks or "not reported by this backend"}


def check_server_log(text: str, rc: int) -> dict:
    check(rc == 0, f"server exited {rc} on SIGTERM")
    for pattern in FORBIDDEN_LOG:
        hit = re.search(pattern, text)
        check(hit is None, f"server log contains {hit and hit.group(0)!r}")
    pinned = import_without_jax("sonata_tpu.serving.drain").DRAIN_PHASES
    phases = re.findall(r"drain: phase=([\w-]+)", text)
    check(tuple(phases) == pinned,
          f"drain phases {phases}, pinned order {list(pinned)}")
    return {"drain_phases": phases,
            "dsp": ("native" if "native library sonata_dsp loaded" in text
                    else "numpy")}


def check_replicas(before: dict, after: dict, n: int) -> dict:
    """Every replica's dispatch counter advanced under the traffic, each
    replica on its own device."""
    dispatches = {k: v - before.get(k, 0.0) for k, v in after.items()
                  if k.startswith("sonata_replica_dispatches{")}
    devices = {k: v for k, v in after.items()
               if k.startswith("sonata_replica_device{")}
    check(len(dispatches) == n and all(v > 0 for v in dispatches.values()),
          f"replica dispatches under the traffic: {dispatches}")
    check(len(set(devices.values())) == n,
          f"replicas sit on devices {sorted(devices.values())}")
    return {"dispatches": dispatches, "devices": devices}


# ---------------------------------------------------------------------------
# parent: the run
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny voice, JAX_PLATFORMS=cpu: debug the "
                         "command off the chip")
    ap.add_argument("--lattice", choices=("full", "minimal", "off"),
                    default="minimal",
                    help="SONATA_WARMUP_LATTICE for the server.  The "
                         "server's own default, full, does not fit a cold "
                         "chip: 36 of 98 shapes were warm when the 600 s "
                         "budget expired (PERF.md)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="pass --replicas N to the server (N chips)")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="pass --mesh-devices N to the server (N chips)")
    ap.add_argument("--no-second-boot", action="store_true",
                    help="skip the warm second boot of the server")
    ap.add_argument("--phase", nargs="+", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return run_child_phase(args.phase)

    set_arms = [k for k in ARM_KNOBS if k in os.environ]
    check(not set_arms, f"arm knobs set in the environment: {set_arms}")
    check(not (args.replicas and args.mesh_devices),
          "--replicas and --mesh-devices are mutually exclusive")
    platform = "cpu" if args.rehearse else "tpu"
    n_chips = args.replicas or args.mesh_devices or 1
    extra_env = {}
    if args.rehearse and n_chips > 1:
        extra_env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_chips}")
    say(f"chip_smoke: platform={platform}"
        + (" (REHEARSAL: tiny voice)" if args.rehearse
           else f" (lessac-high widths, depth cut: {DEPTH_CUT})")
        + f", lattice={args.lattice}, warmup budget={WARMUP_BUDGET_S:.0f} s")

    if args.lattice != "full":
        say(f"NOTE: the server's own default lattice is full; this run "
            f"sets {args.lattice} (a cold full lattice does not fit: see "
            f"--help)")

    # built from what git would commit: drop native libraries and bytecode
    # that a copy of the working tree may have carried along
    removed = []
    for stale in [*REPO.glob("sonata_tpu/native/*.so"),
                  *REPO.rglob("__pycache__")]:
        removed.append(str(stale.relative_to(REPO)))
        shutil.rmtree(stale) if stale.is_dir() else stale.unlink()
    say(f"removed {len(removed)} stale build output(s)")
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    OUT_DIR.mkdir(parents=True)

    cache_dir = Path(import_without_jax(
        "sonata_tpu.utils.jax_cache").compile_cache_dir())
    entries_start = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries_start} entries; "
        + ("JAX_COMPILATION_CACHE_DIR" if os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") else "in-checkout default") + ")")

    summary: dict = {"rehearsal": args.rehearse, "lattice": args.lattice,
                     "warmup_budget_s": WARMUP_BUDGET_S,
                     "cache_dir": str(cache_dir),
                     "cache_entries_start": entries_start}
    voice_dir = tempfile.mkdtemp(prefix="chip_smoke_voice_")
    servers: list = []
    try:
        voice = spawn_phase(
            "write-voice", [voice_dir, "tiny" if args.rehearse else "full"],
            child_env("cpu"), 300.0)
        say(f"voice: {voice['parameters'] / 1e6:.1f} M parameters, hidden "
            f"{voice['hidden_channels']}, decoder "
            f"{voice['upsample_initial_channel']}→{voice['upsample_rates']}")
        summary["voice"] = voice
        cfg = voice["config_path"]

        if args.mesh_devices:
            summary["mesh_check"] = spawn_phase(
                "mesh-check", [cfg, str(args.mesh_devices)],
                child_env(platform, **extra_env), 600.0)
            say(f"mesh: {summary['mesh_check']}")

        server_args = []
        if args.replicas:
            server_args = ["--replicas", str(args.replicas)]
        if args.mesh_devices:
            server_args = ["--mesh-devices", str(args.mesh_devices)]
        pb = import_without_jax("sonata_tpu.frontends.grpc_messages")

        # ---- first boot: cold unless the cache directory came warm ----
        server = Server(cfg, platform, args.lattice, server_args, extra_env,
                        "boot1")
        servers.append(server)
        ready_s = server.wait_ready(WARMUP_BUDGET_S + 180.0)
        boot = parse_boot_log(server.log_text())
        entries_boot1 = cache_entries(cache_dir)
        say(f"server ready in {ready_s:.1f} s: lattice {boot['lattice_mode']}"
            f", {boot['lattice_shapes_warmed']} shapes warmed, batch mode "
            f"{boot['batch_mode']}, {boot['policy']['line']}")
        say(f"server device: {boot['device']}; compile cache "
            f"{boot['cache_dir']}: {entries_start} → {entries_boot1} "
            f"entries")
        device = boot["device"]
        check(device["platform"] == platform,
              f"server runs on {device['platform']}")
        check(device["count"] >= n_chips,
              f"{n_chips} chips asked for, {device['count']} present")
        check(boot["lattice_mode"] == args.lattice,
              f"server warmed lattice mode {boot['lattice_mode']}")
        check(Path(boot["cache_dir"]) == cache_dir,
              f"server caches in {boot['cache_dir']}, not {cache_dir}")
        if not args.rehearse:
            check(entries_boot1 > 0,
                  "a boot on the chip left no compile-cache entries")
        client = Client(pb, server.grpc_port, cfg)
        traffic = drive_traffic(server, client, boot["policy"]["coalesce"],
                                boot["batch_mode"], args.lattice)
        say(f"traffic: {len(traffic['requests'])} requests ok; realtime "
            f"dispatch modes {traffic['realtime_dispatch_modes']}; max rows "
            f"in one concurrent decode dispatch "
            f"{traffic['max_rows_in_a_concurrent_decode_dispatch']}; stage "
            f"{traffic['stream_stage_requests']:.0f} starts in "
            f"{traffic['stream_stage_dispatches']:.0f} dispatches; peak "
            f"device bytes {traffic['peak_bytes_in_use']}")
        say(f"cold compiles after readiness: counter "
            f"{traffic['runtime_cold_compiles_total']:.0f}, dispatches "
            f"{traffic['cold_dispatches_after_readiness']}")
        if args.replicas:
            summary["replicas"] = check_replicas(
                traffic["metrics_before"], traffic["metrics_after"],
                args.replicas)
            say(f"replicas: {summary['replicas']}")
        client.channel.close()
        rc = server.sigterm_and_wait()
        server.kill()
        log1 = check_server_log(server.log_text(), rc)
        say(f"server drained and exited 0; DSP served by: {log1['dsp']}")
        del traffic["metrics_before"], traffic["metrics_after"]
        summary["boot1"] = {"time_to_ready_s": round(ready_s, 1), **boot,
                            "cache_entries_after": entries_boot1,
                            **traffic, **log1}

        # ---- second boot: must find the first boot's cache, be ready sooner
        entries_traffic = cache_entries(cache_dir)
        say(f"traffic after readiness added "
            f"{entries_traffic - entries_boot1} compile-cache entries "
            f"(programs the warmup lattice did not compile)")
        summary["boot1"]["cache_entries_added_by_traffic"] = \
            entries_traffic - entries_boot1
        second = (not args.no_second_boot
                  and elapsed() + ready_s + 120.0 < TIME_LIMIT_S)
        if second:
            server = Server(cfg, platform, args.lattice, server_args,
                            extra_env, "boot2")
            servers.append(server)
            ready2_s = server.wait_ready(WARMUP_BUDGET_S + 180.0)
            entries_boot2 = cache_entries(cache_dir)
            say(f"second boot ready in {ready2_s:.1f} s (first "
                f"{ready_s:.1f} s); cache {entries_traffic} → "
                f"{entries_boot2} entries")
            client = Client(pb, server.grpc_port, cfg)
            row = client.synthesize("batched", pb.Utterance(
                voice_id=client.voice_id, text=SHORT), "boot2")
            client.channel.close()
            rc = server.sigterm_and_wait()
            server.kill()
            check_server_log(server.log_text(), rc)
            # JAX caches only compiles that took >= 1 s, so a program at
            # that threshold is cached by whichever boot took longer: a
            # stray entry is tolerated, a cache that is not found is not
            stray = max(1, (entries_boot1 - entries_start) // 20)
            check(entries_boot2 - entries_traffic <= stray,
                  f"the second boot added {entries_boot2 - entries_traffic}"
                  f" compile-cache entries (first boot: "
                  f"{entries_boot1 - entries_start})")
            if entries_boot1 > entries_start:  # the first boot compiled
                check(ready2_s < ready_s, f"second boot took "
                      f"{ready2_s:.1f} s, first {ready_s:.1f} s")
            summary["boot2"] = {"time_to_ready_s": round(ready2_s, 1),
                                "cache_entries_after": entries_boot2,
                                "request": row}
        else:
            say("second boot skipped ("
                + ("--no-second-boot" if args.no_second_boot
                   else "would not fit the time limit") + ")")
            summary["boot2"] = "skipped"
    finally:
        for s in servers:
            s.kill()
        shutil.rmtree(voice_dir, ignore_errors=True)

    summary["elapsed_s"] = round(elapsed(), 1)
    (OUT_DIR / "summary.json").write_text(json.dumps(summary, indent=1))
    say(f"all phases passed in {elapsed():.0f} s (limit "
        f"{TIME_LIMIT_S:.0f} s); details in "
        f"{(OUT_DIR / 'summary.json').relative_to(REPO)}")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
