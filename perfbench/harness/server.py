"""Spawning and driving the server under test (after ``chip_smoke.py``'s
``Server`` / ``Client``, copied here so that the script can change without
moving the yardstick).  Nothing here imports jax: the server child holds
the chip."""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

from . import wire


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, timeout_s: float = 30.0) -> tuple:
    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as resp:
            return resp.getcode(), resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def parse_metrics(body: str) -> dict:
    """``{series-with-labels: value}`` of a Prometheus text page."""
    out = {}
    for line in body.splitlines():
        m = re.match(r"^(\w+(?:\{[^}]*\})?)\s+(\S+)$", line)
        if m and not line.startswith("#"):
            out[m.group(1)] = float(m.group(2))
    return out


def series_sum(metrics: dict, name: str) -> float:
    return sum(v for k, v in metrics.items()
               if k == name or k.startswith(name + "{"))


def series_max(metrics: dict, name: str):
    values = [v for k, v in metrics.items()
              if k == name or k.startswith(name + "{")]
    return max(values) if values else None


class Server:
    """One boot of the configuration's server command."""

    def __init__(self, root: Path, config: dict, voice_path: str,
                 platform: str, work_dir: Path):
        self.grpc_port, self.metrics_port = free_port(), free_port()
        self.base = f"http://127.0.0.1:{self.metrics_port}"
        self.log_path = Path(work_dir) / "server.log"
        self.memory_path = Path(work_dir) / "memory_stats.json"
        fill = {"voice": voice_path, "grpc_port": str(self.grpc_port),
                "metrics_port": str(self.metrics_port),
                "work_dir": str(work_dir)}
        spec = config["server"]
        self.cmd = [sys.executable] + [a.format(**fill)
                                       for a in spec["argv"]]
        env = dict(os.environ, JAX_PLATFORMS=platform,
                   PERFBENCH_MEMORY_STATS=str(self.memory_path))
        env.update({k: v.format(**fill) for k, v in spec["env"].items()})
        self._log = open(self.log_path, "w")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(self.cmd, env=env, cwd=root,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def wait_ready(self, deadline_s: float) -> float:
        """Seconds from spawn to the first 200 on ``/readyz``."""
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited {self.proc.returncode} before ready:\n"
                    + self.log_text()[-3000:])
            try:
                if http_get(self.base + "/readyz", 10.0)[0] == 200:
                    return time.monotonic() - self.t_spawn
            except OSError:
                pass  # the metrics plane is not listening yet
            if time.monotonic() - self.t_spawn > deadline_s:
                raise RuntimeError(
                    f"server not ready after {deadline_s:.0f} s:\n"
                    + self.log_text()[-3000:])
            time.sleep(0.25)

    def device(self) -> dict:
        """platform / kind / count as the server's own log names them."""
        m = re.search(r"devices: platform=(\S+) device_kind=(.+?) "
                      r"count=(\d+)", self.log_text())
        if m is None:
            raise RuntimeError("no 'devices:' line in the server log")
        return {"platform": m.group(1), "kind": m.group(2).strip(),
                "count": int(m.group(3))}

    def metrics(self) -> dict:
        code, body = http_get(self.base + "/metrics")
        if code != 200:
            raise RuntimeError(f"/metrics answered {code}")
        return parse_metrics(body)

    def traces(self) -> list:
        code, body = http_get(self.base + "/debug/traces", 60.0)
        if code != 200:
            raise RuntimeError(f"/debug/traces answered {code}")
        return json.loads(body)["traces"]

    def profile(self, seconds: float) -> dict:
        """Blocks for ``seconds``: the server traces its own device."""
        t0 = time.time()
        code, body = http_get(
            f"{self.base}/debug/profile?seconds={seconds}", seconds + 120.0)
        if code != 200:
            raise RuntimeError(f"/debug/profile answered {code}: {body}")
        return dict(json.loads(body), wall_start=t0, wall_end=time.time())

    def stop(self, timeout_s: float = 60.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()

    def memory_peaks(self):
        """After :meth:`stop`: per device, the two peaks the runtime counts
        apart (``harness/serve.py`` wrote them as the server left), or
        ``None`` where the backend keeps no memory counters (the CPU)."""
        if not self.memory_path.exists():
            raise RuntimeError("the server left no memory counters:\n"
                               + self.log_text()[-2000:])
        devices = json.loads(self.memory_path.read_text())
        if all(d["stats"] is None for d in devices):
            return None
        return [{"device": d["device"],
                 "in_use": int(d["stats"]["peak_bytes_in_use"]),
                 "reserved": int(d["stats"]["peak_bytes_reserved"])}
                for d in devices]


class Client:
    """gRPC calls as raw bytes, one channel."""

    def __init__(self, port: int):
        import grpc

        self.channel = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_receive_message_length", 64 << 20)])

    def _call(self, method: str, stream: bool):
        factory = (self.channel.unary_stream if stream
                   else self.channel.unary_unary)
        return factory(wire.SERVICE + method,
                       request_serializer=lambda b: b,
                       response_deserializer=lambda b: b)

    def load_voice(self, config_path: str) -> dict:
        return wire.voice_info(self._call("LoadVoice", False)(
            wire.voice_path(config_path), timeout=120.0))

    def set_options(self, voice_id: str, **options) -> None:
        """``speaker``, ``noise_scale``, ``noise_w``: voice-wide, as the
        stock RPC sets them."""
        self._call("SetSynthesisOptions", False)(
            wire.synthesis_options(voice_id, **options), timeout=60.0)

    def synthesize(self, request: bytes, request_id: str,
                   realtime: bool = False, timeout_s: float = 300.0):
        """Yields ``(seconds since the call, int16 PCM bytes)`` for each
        message of the stream."""
        t0 = time.monotonic()
        method = ("SynthesizeUtteranceRealtime" if realtime
                  else "SynthesizeUtterance")
        for msg in self._call(method, True)(
                request, timeout=timeout_s,
                metadata=(("x-request-id", request_id),)):
            yield time.monotonic() - t0, wire.wav_samples(msg)

    def close(self) -> None:
        self.channel.close()
