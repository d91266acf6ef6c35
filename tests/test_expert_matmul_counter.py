"""What a unit voice says of its expert products (PR 34): every step-group
and every prefill span states ``expert_matmul``, and
``sonata_moe_expert_matmul_total{impl, program}`` counts the launches.  On
the CPU a program runs ``ragged_dot``; a voice whose programs run this
repo's kernel is made here by steering the two names ``lfm2`` reads (the
decision and the product, the kernel in interpret mode), not by an option
of the program."""

import importlib
import json
from pathlib import Path

import pytest

from perfbench.harness import lfm2gen, sdargen
from sonata_tpu.models import from_config_path, lfm2
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.serving import tracing
from sonata_tpu.serving.metrics import MetricsRegistry

gm = importlib.import_module("sonata_tpu.ops.grouped_matmul")
DATA = Path(__file__).resolve().parent / "perfbench/data"
TEXTS = ["one short row.", "and another one."]


def series(registry) -> dict:
    out = {}
    for line in registry.render().splitlines():
        if line.startswith("sonata_moe_expert_matmul_total{"):
            labels, value = line.split("{")[1].split("} ")
            labels = dict(p.split("=") for p in labels.split(","))
            out[labels["impl"].strip('"'),
                labels["program"].strip('"')] = float(value)
    return out


@pytest.mark.parametrize("impl", ["ragged_dot", "grouped"])
@pytest.mark.parametrize("tiny, writer", [("lfm2-tiny.json", lfm2gen),
                                          ("sdar-tiny.json", sdargen)],
                         ids=["lfm2", "sdar"])
def test_spans_and_series_say_what_the_expert_products_ran(
        tiny, writer, impl, tmp_path, monkeypatch):
    if impl == "grouped":
        monkeypatch.setattr(lfm2, "implementation",
                            lambda *shape: "grouped")
        monkeypatch.setattr(
            lfm2, "grouped_matmul", lambda x, w, sizes, **kw:
            gm.grouped_matmul_kernel(x, w, sizes, gm.Tiles(16, w.shape[2]),
                                     interpret=True, **kw))
    monkeypatch.setenv("SONATA_AR_SLOTS", "2")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "256")
    config = json.loads((DATA / tiny).read_text())
    voice = from_config_path(writer.write_tensors(tmp_path, config))
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    tracer = tracing.default_tracer()
    tracer.clear()
    before, steps_before = series(registry), stats.steps
    assert set(before) == {(i, p) for i in ("grouped", "ragged_dot")
                           for p in ("prefill", "step")}
    # a backbone whose step carries arrivals launches no prefill program
    rides = voice.backbone.build_step_admit is not None
    try:
        assert voice.expert_matmul == impl
        for k, text in enumerate(TEXTS):
            with tracer.trace_request("test", request_id=f"row-{k}"):
                audio = voice.speak_batch(list(voice.phonemize_text(text)))
            assert len(audio[0].samples) > 0
    finally:
        voice.close()       # the loop's last group is recorded as it ends
    traces = {t.request_id: t for t in tracer.recent_traces()}
    for k in range(len(TEXTS)):
        (prefill,) = [s.attrs for s in traces[f"row-{k}"].spans_snapshot()
                      if s.attrs.get("kind") == "prefill"]
        assert prefill["expert_matmul"] == impl
        assert prefill["admit"] == ("step" if rides else "apart")
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups and {g["expert_matmul"] for g in groups} == {impl}
    after = series(registry)
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    want = {(impl, "step"): float(stats.steps - steps_before)}
    if not rides:
        want[impl, "prefill"] = float(len(TEXTS))
    assert moved == want
    assert stats.steps > steps_before
