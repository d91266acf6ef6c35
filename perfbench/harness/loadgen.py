"""The load generator: a process of its own, few threads, no jax.

    python -m perfbench.harness.loadgen <job.json>

One general generator reads a traffic file's parameters.  ``closed_paragraphs``
runs ``callers`` closed loops; each takes the next paragraph of the seeded
schedule, sends it as one request and waits for the whole answer.  The loops
never pause: warm-up is the part of the run before ``WARM`` is announced,
and the window is the ``seconds`` after it.  Warm-up ends when the whole
list has been sent once, ``warmup.min_requests`` requests have been
answered (the frame-budget estimator starts high, from the server's boot
utterance, and decays 0.5 % a dispatch) and ``settle_dispatches`` requests
have passed since the last slow one, which is how a compile looks from
outside.

Once the window has closed and every caller has come back, a sample of
the requests the window finished is drawn from the seed over all of them,
the one with the longest sentence in it, and sent again through the same
callers with the voice's noise set to ``check.inference`` (zero): the stock
RPCs carry no seed, so only noise-free synthesis can be held against a
reference sample by sample.  Same RPC, same compiled programs, same batch
and lengths; the replay is outside the window and counts for nothing but
the comparison.

Lines on standard output: ``WARM {...}`` when the window opens, ``DONE
{...}`` with the window's records and the replayed sample when all of
that is over.
"""

from __future__ import annotations

import json
import random
import sys
import threading
import time
from pathlib import Path

import numpy as np

from . import shapes, textgen, wire
from .server import Client


#: a warm-up request counts as slow (it compiled, or loaded a program from
#: the persistent cache) when it took this many times the median of the
#: warm-up requests before it; the first few always count as slow
SLOW_FACTOR = 2.5
SLOW_MIN_SAMPLES = 6


class Generator:
    def __init__(self, job: dict):
        self.job = job
        self.traffic = job["traffic"]
        if self.traffic["kind"] != "closed_paragraphs":
            raise ValueError(f"no generator for traffic of kind "
                             f"{self.traffic['kind']!r}")
        self.seed = int(job["seed"])
        self.lexicon = textgen.Lexicon(Path(job["root"])
                                       / self.traffic["words"])
        self.order = textgen.schedule(self.traffic, self.seed)
        self.client = Client(job["grpc_port"])
        self.lock = threading.Lock()
        self.next_seq = 0
        self.records: list = []
        self.stop = threading.Event()
        self.t_origin = time.monotonic()
        self.t_window = None  # set when warm
        self.warm_requests = 0
        self.since_cold = 0
        self.cold = 0
        self.warm_latencies: list = []
        self.errors: list = []

    def now(self) -> float:
        return time.monotonic() - self.t_origin

    def text_of(self, seq: int) -> tuple:
        para = self.order[seq % len(self.order)]
        rng = random.Random(self.seed * 1000003 + seq)
        return para, textgen.paragraph_text(
            self.lexicon, self.traffic["paragraphs"][para], rng)

    def send(self, seq: int, rid: str) -> tuple:
        """One request of the schedule: the record and its audio."""
        para, sentences = self.text_of(seq)
        request = wire.utterance(self.voice_id, " ".join(sentences),
                                 self.traffic["synthesis_mode"])
        t_start = self.now()
        ok, chunks, error = True, [], None
        try:
            for _, pcm in self.client.synthesize(request, rid):
                chunks.append(pcm)
        except Exception as e:  # a failed request is counted, not raised
            ok, error = False, f"{type(e).__name__}: {e}"[:300]
        t_end = self.now()
        ok = ok and len(chunks) == len(sentences) and all(
            len(c) and len(c) % 2 == 0 for c in chunks)
        record = {"seq": seq, "paragraph": para, "rid": rid, "ok": ok,
                  "t_start": t_start, "t_end": t_end,
                  "samples": [len(c) // 2 for c in chunks]}
        if error:
            record["error"] = error
        return record, sentences, chunks

    def one_request(self, seq: int) -> None:
        with self.lock:
            in_window = self.t_window is not None
        record, _, _ = self.send(seq, f"pb-{self.seed}-{seq}")
        with self.lock:
            self.records.append(record)
        if not in_window:
            if not record["ok"]:
                # the cell's traffic may not fail: stop at once and say why
                self.errors.append(
                    f"warm-up request {record['rid']} failed: "
                    f"{record.get('error') or record['samples']}")
                self.stop.set()
            self.after_warm_request(record["t_end"] - record["t_start"],
                                    record["ok"])

    def after_warm_request(self, latency: float, ok: bool) -> None:
        """The stock server's dispatch spans say nothing of compiles, so a
        compile is seen from outside: by how long the request took."""
        with self.lock:
            before = sorted(self.warm_latencies)
            self.warm_latencies.append(latency)
            self.warm_requests += 1
            slow = (not ok or len(before) < SLOW_MIN_SAMPLES
                    or latency > SLOW_FACTOR * before[len(before) // 2])
            if slow:
                self.cold += 1
                self.since_cold = 0
            else:
                self.since_cold += 1
            warm = self.traffic["warmup"]
            if (self.t_window is None
                    and self.warm_requests >= max(
                        len(self.traffic["paragraphs"]),
                        int(warm.get("min_requests", 0)))
                    and self.since_cold >= int(warm["settle_dispatches"])):
                self.t_window = self.now()
                print("WARM " + json.dumps({
                    "t_window": self.t_window, "requests": self.warm_requests,
                    "slow_requests": self.cold,
                    "median_latency_s": before[len(before) // 2]}),
                    flush=True)

    def caller(self) -> None:
        try:
            while not self.stop.is_set():
                with self.lock:
                    seq = self.next_seq
                    self.next_seq += 1
                self.one_request(seq)
        except Exception as e:
            self.errors.append(f"{type(e).__name__}: {e}")
            self.stop.set()

    def run(self) -> dict:
        job = self.job
        info = self.client.load_voice(job["voice_path"])
        self.voice_id, self.sample_rate = info["voice_id"], info["sample_rate"]
        speaker = None
        if job["voice"]["num_speakers"] > 1:
            speaker = random.Random(self.seed * 31 + 5).randrange(
                job["voice"]["num_speakers"])
            self.client.set_options(self.voice_id, speaker=str(speaker))
        threads = [threading.Thread(target=self.caller, daemon=True)
                   for _ in range(int(self.traffic["callers"]))]
        for t in threads:
            t.start()
        max_warm = float(self.traffic["warmup"]["max_seconds"])
        while self.t_window is None and not self.stop.is_set():
            if self.now() > max_warm:
                self.errors.append(f"not warm after {max_warm:.0f} s")
                self.stop.set()
            time.sleep(0.05)
        if self.t_window is not None:
            end = self.t_window + float(job["seconds"])
            while self.now() < end and not self.stop.is_set():
                time.sleep(min(0.05, max(end - self.now(), 0.0)))
        self.stop.set()
        for t in threads:
            t.join(timeout=600.0)
        try:
            return self.result(speaker)
        finally:
            self.client.close()

    def window_records(self) -> list:
        t0, t1 = self.t_window, self.t_window + float(self.job["seconds"])
        return [r for r in self.records if t0 <= r["t_end"] <= t1]

    def replay_sample(self, window: list) -> tuple:
        """``check.requests`` of the window's finished requests, drawn from
        the seed over all of them with the longest sentence's in it, sent
        again noise-free through ``callers`` threads."""
        paragraphs = self.traffic["paragraphs"]
        done = sorted((r for r in window if r["ok"]),
                      key=lambda r: r["seq"])
        if not done:
            return [], {}
        longest = max(done, key=lambda r: (max(paragraphs[r["paragraph"]]),
                                           -r["seq"]))
        rest = [r for r in done if r is not longest]
        random.Random(self.seed * 7919 + 3).shuffle(rest)
        check = self.traffic["check"]
        chosen = [longest] + rest[:max(int(check["requests"]) - 1, 0)]
        self.client.set_options(self.voice_id, **check["inference"])
        kept, todo = {}, list(chosen)

        def worker():
            while True:
                with self.lock:
                    if not todo:
                        return
                    r = todo.pop()
                record, sentences, chunks = self.send(
                    r["seq"], f"pb-check-{self.seed}-{r['seq']}")
                with self.lock:
                    kept[r["seq"]] = (record, sentences, chunks)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(int(self.traffic["callers"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        sampled, arrays = [], {}
        for seq in sorted(kept):
            record, sentences, chunks = kept[seq]
            sampled.append({"seq": seq, "paragraph": record["paragraph"],
                            "rid": record["rid"], "ok": record["ok"],
                            "sentences": sentences})
            for i, pcm in enumerate(chunks):
                arrays[f"{seq}_{i}"] = np.frombuffer(pcm, "<i2")
        return sampled, arrays

    def result(self, speaker) -> dict:
        if self.errors or self.t_window is None:
            return {"error": "; ".join(self.errors) or "never warm"}
        t0, t1 = self.t_window, self.t_window + float(self.job["seconds"])
        window = self.window_records()
        started = [r for r in self.records if t0 <= r["t_start"] <= t1]
        audio_s = sum(sum(r["samples"]) for r in window
                      if r["ok"]) / self.sample_rate
        sampled, arrays = self.replay_sample(window)
        audio_path = Path(self.job["out_dir"]) / "sampled_audio.npz"
        with open(audio_path, "wb") as f:
            np.savez(f, **arrays)
        out = {"t_window": t0, "seconds": self.job["seconds"],
                "sample_rate": self.sample_rate, "speaker": speaker,
                "attempted": len(started),
                "failed": sum(1 for r in started if not r["ok"]),
                "completed": len(window), "audio_s": audio_s,
                "records": window, "sampled": sampled,
                # answers in each second since the generator began, the
                # warm-up's too: a stall shows as a run of small counts
                "answers_per_second": [
                    sum(1 for r in self.records if int(r["t_end"]) == k)
                    for k in range(int(t1) + 1)],
                "sampled_audio": str(audio_path),
                "wall_origin": time.time() - self.now()}
        if self.job["voice"].get("frame_budget_estimator"):
            # a model of the stock path's estimator: only where the
            # voice's writer says the path has one
            out["estimator_replay"] = shapes.replay_estimator(
                self.records, self.traffic["paragraphs"],
                int(self.job["voice"]["samples_per_frame"]), t0, t1)
        return out


def main(argv: list) -> int:
    job = json.loads(Path(argv[1]).read_text())
    result = Generator(job).run()
    print("DONE " + json.dumps(result), flush=True)
    return 1 if "error" in result else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
