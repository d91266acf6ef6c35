"""What a unit voice says of its expert products (PR 34): every step-group
and every prefill span states ``expert_matmul``, and
``sonata_moe_expert_matmul_total{impl, program}`` counts the launches.  On
the CPU a program runs ``ragged_dot``; a voice whose programs run this
repo's kernel is made here by steering the two names ``unit_layers`` reads (the
decision and the product, the kernel in interpret mode), not by an option
of the program."""

import importlib
import json
from pathlib import Path

import pytest

from perfbench.harness import lfm2gen, pangugen, sdargen
from sonata_tpu.models import from_config_path, unit_layers
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
                                          ("sdar-tiny.json", sdargen),
                                          ("pangu-tiny.json", pangugen)],
                         ids=["lfm2", "sdar", "pangu"])
def test_spans_and_series_say_what_the_expert_products_ran(
        tiny, writer, impl, tmp_path, monkeypatch):
    if impl == "grouped":
        monkeypatch.setattr(unit_layers, "implementation",
                            lambda *shape: "grouped")
        monkeypatch.setattr(
            unit_layers, "grouped_matmul", lambda x, w, sizes, **kw:
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


@pytest.mark.parametrize("config, slots, held_of, step, siblings", [
    ("lfm2/lfm2-24b-a2b.json", 64, None, 256, True),
    ("sdar/sdar-30b-a3b.json", 64, None, 2048, True),
    ("nemotron/nemotron-3-nano-30b-a3b.json", 256, (0, 64), 1536, True),
    ("pangu/openpangu-ultra-moe-718b.json", 256, (0, 8), 256, False),
], ids=["lfm2", "sdar", "nemotron", "pangu"])
def test_what_a_program_says_of_a_thin_share_at_the_cells_shapes(
        config, slots, held_of, step, siblings, monkeypatch):
    """``expert_matmul`` at the cells' real widths, as on a TPU: the three
    siblings' rows are all their assignments, as before a layer could be
    told of a thin share; 8 of 256 experts held hand on 256 rows where an
    untold layer handed on 2048, which the kernel's rule leaves to
    ``ragged_dot``; every carrying step of the lattice says ``grouped``
    too."""
    from sonata_tpu.models import unit_voice
    from sonata_tpu.utils.buckets import TEXT_BUCKETS

    from perfbench.harness import nemotrongen
    data = json.loads((DATA.parents[2] / "perfbench/configs"
                       / config).read_text())
    writer = {"lfm2": lfm2gen, "sdar": sdargen, "nemotron": nemotrongen,
              "pangu": pangugen}[config.split("/")[0]]
    built = unit_voice.make_backbone(
        writer.backbone(data), {"first_id": 256, "stop_id": 511,
                                "mask_id": 300, "block_length": 4})
    cfg, tokens = built.cfg, slots * built.block_length
    assert built.held == held_of
    assert unit_layers.held_rows(cfg, tokens, built.held) == step
    assert (step == tokens * cfg.num_experts_per_tok) == siblings
    monkeypatch.setattr(gm, "_tiles_here", gm.tile_rule)
    assert unit_layers.expert_matmul(cfg, tokens, built.held) == "grouped"
    if not siblings:
        # told nothing, the layer would hand all 2048 rows to ragged_dot
        assert gm.tile_rule(tokens * cfg.num_experts_per_tok, 8, 7680, 4096,
                            unit_layers.BF16) is None
        assert {unit_layers.expert_matmul(cfg, slots + t, built.held)
                for t in TEXT_BUCKETS} == {"grouped"}
