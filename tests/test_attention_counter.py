"""What a unit voice says of its attention (PR 37): every step-group and
every prefill span states ``attention``, and
``sonata_attention_impl_total{impl, program}`` counts the launches.  On the
CPU a step reads the slots' keys and values by the einsum; a voice whose
step runs this repo's kernel is made here by steering the one name both
the decision and the reader ask (``slot_attention._tiles_here``, the kernel
then in interpret mode), not by an option of the program.  A prefill
attends over its own prompt and says ``einsum`` whatever the step runs.
Since PR 45 a step group also states ``kv_places_fetched``, the places that
reader moved for the live rows over the layers that keep keys and values,
and ``sonata_kv_places_fetched_total`` sums it."""

import dataclasses
import functools
import importlib
import json
from pathlib import Path

import pytest

from perfbench.harness import lfm2gen, sdargen
from sonata_tpu.models import from_config_path
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.serving import tracing
from sonata_tpu.serving.metrics import MetricsRegistry
from tests.voices import row_sums

sa = importlib.import_module("sonata_tpu.ops.slot_attention")
DATA = Path(__file__).resolve().parent / "perfbench/data"
TEXTS = ["one short row.", "and another one."]


def series(registry) -> dict:
    out = {}
    for line in registry.render().splitlines():
        if line.startswith("sonata_attention_impl_total{"):
            labels, value = line.split("{")[1].split("} ")
            labels = dict(p.split("=") for p in labels.split(","))
            out[labels["impl"].strip('"'),
                labels["program"].strip('"')] = float(value)
    return out


@pytest.mark.parametrize("tiny, writer, impl", [
    ("lfm2-tiny.json", lfm2gen, "einsum"),
    ("sdar-tiny.json", sdargen, "einsum"),
    ("sdar-tiny.json", sdargen, "slot_kernel")],
    ids=["lfm2-einsum", "sdar-einsum", "sdar-slot_kernel"])
def test_spans_and_series_say_what_the_attention_ran(
        tiny, writer, impl, tmp_path, monkeypatch):
    config = json.loads((DATA / tiny).read_text())
    if impl == "slot_kernel":
        # two heads of 64 fill one lane group: a shape the kernel takes
        config["head_dim"] = 64
        monkeypatch.setattr(sa, "_tiles_here", lambda *shape: sa.Tiles(128))
        monkeypatch.setattr(sa, "slot_attention_kernel", functools.partial(
            sa.slot_attention_kernel, interpret=True))
    monkeypatch.setenv("SONATA_AR_SLOTS", "2")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "256")
    voice = from_config_path(writer.write_tensors(tmp_path, config))
    # the lengths the loop looks up in the description's table

    class Asked(list):
        def __getitem__(self, attended):
            asked.append(attended)
            return super().__getitem__(attended)

    asked, described = [], voice.description
    voice.description = dataclasses.replace(described,
                                            rows=Asked(described.rows))

    def counting(attended):
        return row_sums(described, attended)["kv_places_fetched"]
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    tracer = tracing.default_tracer()
    tracer.clear()
    before, steps_before = series(registry), stats.steps
    fetched_before = stats.kv_places_fetched
    assert set(before) == {(i, p) for i in tracing.ATTENTION_IMPLS
                           for p in ("prefill", "step")}
    # a backbone whose step carries arrivals launches no prefill program
    rides = voice.backbone.build_step_admit is not None
    try:
        assert voice.attention == impl
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
        assert prefill["attention"] == "einsum"
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups and {g["attention"] for g in groups} == {impl}
    after = series(registry)
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert stats.steps > steps_before
    want = {} if rides else {("einsum", "prefill"): float(len(TEXTS))}
    want[impl, "step"] = want.get((impl, "step"), 0.0) + float(
        stats.steps - steps_before)
    assert moved == want
    # the places the reader moved: every attention layer's, whole chunks of
    # 128 a row under the kernel, all 256 of a slot under the einsum
    layers = voice.backbone.attention_layers
    chunk = 128 if impl == "slot_kernel" else 256
    assert layers > 0 and [counting(n) for n in (0, 1, 128, 129)] == [
        0, layers * chunk, layers * chunk, layers * 256]
    fetched = sum(g["kv_places_fetched"] for g in groups)
    assert sum(asked) == sum(g["kv_positions"] for g in groups)
    assert fetched == sum(counting(n) for n in asked) \
        >= layers * sum(asked) > 0
    assert stats.kv_places_fetched == fetched_before + fetched
    assert f"sonata_kv_places_fetched_total {stats.kv_places_fetched}\n" \
        in registry.render()
    assert all(g["latent_places_fetched"] == 0 for g in groups)
