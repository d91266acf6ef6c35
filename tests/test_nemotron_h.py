"""The Nemotron-H backbone (``sonata_tpu/models/nemotron_h.py``) against the
plain reference (``perfbench/reference/nemotron_ref.py``) at a tiny size on
the CPU, float32, seeded: prefill then steps through the slot's state
against the reference's full pass, the chunked scan against the recurrence,
a slot reused, the two shares of an expert layer against the uncut layer,
the experts' layout in whole lanes, what the new configuration field leaves
of the sibling programs, and the voice with what its loop records."""

import dataclasses
import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from perfbench.harness import lfm2gen, nemotrongen, parts, sdargen
from sonata_tpu.models import from_config_path, lfm2, nemotron_h, sdar, \
    unit_layers
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.models.unit_backbone import routes_of
from sonata_tpu.serving import tracing
from sonata_tpu.serving.metrics import MetricsRegistry

gm = importlib.import_module("sonata_tpu.ops.grouped_matmul")
ROOT = Path(__file__).resolve().parent.parent
DATA = ROOT / "tests/perfbench/data"
CONFIG = json.loads((DATA / "nemotron-tiny.json").read_text())
REAL = json.loads((ROOT / "perfbench/configs/nemotron/"
                   "nemotron-3-nano-30b-a3b.json").read_text())
BB = nemotrongen.backbone(CONFIG)
CFG = nemotron_h.NemotronConfig.from_dict(BB)
UNITS = unit_layers.UnitIds(256, 511)
LAYERS = len(CFG.pattern)
#: prompts shorter than, equal to and longer than one chunk (8), one that
#: ends on a chunk's edge, and one that fills its text bucket
PROMPTS = {"shorter": (5, 16), "one_chunk": (8, 16), "longer": (19, 32),
           "two_chunks": (16, 32), "whole_bucket": (32, 32)}
ref = parts.load_file(ROOT / "perfbench/reference/nemotron_ref.py")


def wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(autouse=True)
def float32_products(monkeypatch):
    """The program's products take bfloat16 inputs; here they take float32
    at ``highest``, so that it can be held to the reference to rounding."""
    monkeypatch.setattr(unit_layers, "BF16", jnp.float32)
    monkeypatch.setattr(nemotron_h, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def raw():
    return [wide(nemotrongen.draw_layer(CONFIG, i)) for i in range(LAYERS)]


@pytest.fixture(scope="module")
def params(raw):
    return {"embed": wide(nemotrongen.draw(CONFIG, "embed")),
            "head": wide(nemotrongen.draw(CONFIG, "head")),
            "norm_f": wide(nemotrongen.draw(CONFIG, "norm_f")),
            "layers": [nemotron_h.pack_layer(r) for r in raw]}


def prompt(n: int, bucket: int, seed: int = 0):
    ids = np.random.default_rng(seed + n).integers(0, 256, size=(n,))
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = ids
    return ids.tolist(), jnp.asarray(padded)


def run_row(params, cache, slot: int, ids: list, padded, steps: int):
    """Prefill into ``slot`` and ``steps`` greedy steps of that slot alone:
    the logits of every launch, the tokens fed, and the cache."""
    cache, logits, _ = nemotron_h.prefill(
        params, cache, padded, jnp.int32(len(ids)), jnp.int32(slot),
        jnp.float32(0.0), jax.random.PRNGKey(0), cfg=CFG, units=UNITS)
    live = jnp.arange(cache["pos"].shape[0]) == slot
    got, tokens = [np.asarray(logits)], list(ids)
    for k in range(steps):
        tokens.append(int(cache["token"][slot]))
        cache, out, _ = nemotron_h.step(
            params, cache, live, jnp.zeros(live.shape), k, cfg=CFG,
            units=UNITS)
        got.append(np.asarray(out[slot]))
    return np.stack(got), tokens, cache


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_prefill_then_steps_give_the_references_full_pass(name, raw, params):
    """Logits of the prefill and of 12 steps through the slot's state
    (recurrent states, convolution columns, keys and values) against one
    whole pass of the reference over prompt + units; the experts chosen
    too."""
    n, bucket = PROMPTS[name]
    ids, padded = prompt(n, bucket)
    got, tokens, cache = run_row(params, nemotron_h.new_cache(CFG, 3, 64), 1,
                                 ids, padded, 12)
    want, routes = ref.forward(
        jnp.asarray(tokens), BB, params["embed"], params["head"],
        params["norm_f"], lambda i: raw[i], held=CFG.held)
    np.testing.assert_allclose(got, np.asarray(want)[n - 1:], rtol=0,
                               atol=2e-4)
    served = routes_of(CFG, np.asarray(cache["routes"][1]))[:len(tokens)]
    assert np.array_equal(np.sort(served, -1), np.sort(np.asarray(routes),
                                                       -1))
    assert served.shape[1:] == (3, 2) and served.max() < 8


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_the_chunked_scan_is_the_recurrence(name, raw):
    """``mamba_seq`` (products inside chunks of 8) against ``mamba_step``
    run a position at a time from a zero state: the layer's output at every
    real position, and the state and the columns it leaves after ``n``."""
    n, bucket = PROMPTS[name]
    p = nemotron_h.pack_layer(raw[0])["mixer"]
    u = jnp.asarray(np.random.default_rng(n).standard_normal(
        (bucket, CFG.hidden_size)), jnp.float32)
    out, state, conv = nemotron_h.mamba_seq(u, p, CFG, jnp.int32(n))
    s = jnp.zeros((1, CFG.mamba_num_heads, CFG.mamba_head_dim,
                   CFG.ssm_state_size))
    c = jnp.zeros((1, CFG.conv_kernel - 1, CFG.conv_dim))
    for k in range(n):
        y, s, c = nemotron_h.mamba_step(u[k:k + 1], p, CFG, s, c)
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(y[0]),
                                   rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s[0]), rtol=0,
                               atol=2e-6)
    assert np.array_equal(np.asarray(conv), np.asarray(c[0]))
    # and the reference's own recurrence leaves the same
    _, (ref_state, ref_conv) = ref.mamba(u[:n], raw[0]["mixer"], BB)
    np.testing.assert_allclose(np.asarray(state), np.asarray(ref_state),
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(conv), np.asarray(ref_conv),
                               rtol=0, atol=1e-6)


def test_a_slot_a_row_has_left_gives_what_a_fresh_slot_gives(params):
    """Keys and values are masked by position, a recurrent state is not:
    the second row of a slot must not see the first one's.  To the bit."""
    first, first_padded = prompt(19, 32)
    second, second_padded = prompt(11, 16, seed=7)
    _, _, used = run_row(params, nemotron_h.new_cache(CFG, 2, 64), 1, first,
                         first_padded, 9)
    assert float(jnp.abs(used["ssm"][0][1]).max()) > 0.0
    again, tokens, used = run_row(params, used, 1, second, second_padded, 9)
    fresh, fresh_tokens, clean = run_row(
        params, nemotron_h.new_cache(CFG, 2, 64), 1, second, second_padded, 9)
    assert tokens == fresh_tokens and np.array_equal(again, fresh)
    for a, b in zip(used["ssm"] + used["conv"], clean["ssm"] + clean["conv"]):
        assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_an_empty_slot_costs_no_expert_product_and_does_not_move(params):
    cache = nemotron_h.new_cache(CFG, 3, 64)
    ids, padded = prompt(8, 16)
    _, _, cache = run_row(params, cache, 2, ids, padded, 0)
    before = {k: np.asarray(cache[k]) for k in ("token", "pos", "count")}
    cache, _, load = nemotron_h.step(
        params, cache, jnp.asarray([False, False, True]), jnp.zeros((3,)), 0,
        cfg=CFG, units=UNITS)
    load = np.asarray(load)
    # one live row: 2 assignments a layer, of which the held experts' share
    assert load.shape == (3, 5) and load[:, 2].tolist() == [2, 2, 2]
    assert (load[:, 4] <= load[:, 2]).all() and (load[:, 3] <= 2).all()
    for k in ("token", "pos", "count"):
        assert np.array_equal(np.asarray(cache[k])[:2], before[k][:2])
    assert int(cache["pos"][2]) == before["pos"][2] + 1


def uncut(raw_mixer):
    """A layer's mixer with all 8 routed experts: the held four, and four
    more drawn for the other chip."""
    rng = np.random.default_rng(11)
    more = {k: jnp.asarray(rng.uniform(-1, 1, raw_mixer[k].shape)
                           * float(jnp.abs(raw_mixer[k]).max()), jnp.float32)
            for k in ("w_up", "w_down")}
    return dict(raw_mixer, **{k: jnp.concatenate([raw_mixer[k], more[k]])
                              for k in more})


def test_the_two_shares_add_up_to_the_uncut_layer_the_shared_expert_once(
        raw):
    """``held = (0, E/2)`` and ``(E/2, E/2)``, what every chip computes
    alike (the shared expert) counted once, against the reference's whole
    layer; each share against the reference's share."""
    whole = uncut(raw[1]["mixer"])
    u = jnp.asarray(np.random.default_rng(5).standard_normal(
        (13, CFG.hidden_size)), jnp.float32)
    want, chosen = ref.experts(u, whole, BB)
    shared = ref.expert(u, whole["shared_up"], whole["shared_down"])
    total = -shared
    for first in (0, 4):
        half = dict(whole, w_up=whole["w_up"][first:first + 4],
                    w_down=whole["w_down"][first:first + 4])
        cfg = dataclasses.replace(CFG, held=(first, 4))
        got, routes, load = unit_layers.moe_ffn(
            u, nemotron_h.pack_layer({"norm": raw[1]["norm"],
                                      "mixer": half})["mixer"], cfg, cfg.held)
        part, _ = ref.experts(u, half, BB, held=(first, 4))
        np.testing.assert_allclose(np.asarray(got), np.asarray(part), rtol=0,
                                   atol=2e-5)
        assert np.array_equal(np.sort(np.asarray(routes), -1),
                              np.sort(np.asarray(chosen), -1))
        inside = (np.asarray(routes) >= first) & (np.asarray(routes)
                                                  < first + 4)
        assert np.asarray(load).tolist()[2:] == [26, len(set(np.asarray(
            routes)[inside].tolist())), int(inside.sum())]
        total = total + got
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=4e-5)
    # both shares hold some of the work, neither all of it
    assert 0 < int(inside.sum()) < 26


def test_the_router_is_lfm2s_to_the_letter_but_for_the_published_epsilon(
        raw):
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (9, CFG.hidden_size)), jnp.float32)
    p = nemotron_h.pack_layer(raw[1])["mixer"]
    chosen, weights = unit_layers.route(u, p, CFG)
    want, want_weights, _ = ref.route(u, raw[1]["mixer"], BB)
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want_weights),
                               rtol=2e-6)
    # normalised to 1, times routed_scaling_factor
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    # the bias moves the choice and not the weights
    biased = dict(p, expert_bias=p["expert_bias"].at[0].add(10.0))
    moved, _ = unit_layers.route(u, biased, CFG)
    assert (np.asarray(moved) == 0).any(-1).all()


# -- the experts' layout -----------------------------------------------------

def test_experts_lie_in_whole_lanes_and_give_the_same_to_the_last_bit(raw):
    """Zero columns of ``w_up`` and zero rows of ``w_down``: the products at
    a width the lanes do not divide (24 here, 1856 on the chip) become
    shapes the kernel takes, and nothing moves: ``ragged_dot`` on the
    published width, ``ragged_dot`` and the kernel on the padded one."""
    mixer = raw[1]["mixer"]
    w13, w2 = unit_layers.pad_experts(mixer["w_up"], mixer["w_down"])
    assert w13.shape == (4, 64, 128) and w2.shape == (4, 128, 64)
    assert gm.lanes(1856) == 1920 and gm.lanes(1920) == 1920
    p = nemotron_h.pack_layer(raw[1])["mixer"]
    assert p["w13"].shape == (4, 64, 128)
    u = jnp.asarray(np.random.default_rng(3).standard_normal(
        (24, CFG.hidden_size)), jnp.float32)
    padded = unit_layers.moe_ffn(u, p, CFG, CFG.held)
    plain = unit_layers.moe_ffn(
        u, dict(p, w13=mixer["w_up"], w2=mixer["w_down"]), CFG, CFG.held)
    for a, b in zip(padded, plain):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the kernel itself, bfloat16 in, at the padded width
    x = jnp.asarray(np.random.default_rng(4).standard_normal((48, 64)),
                    jnp.bfloat16)
    sizes = jnp.asarray([10, 0, 21, 9], jnp.int32)
    want = lax.ragged_dot(x, mixer["w_up"].astype(jnp.bfloat16), sizes,
                          preferred_element_type=jnp.float32)
    got = gm.grouped_matmul_kernel(x, w13.astype(jnp.bfloat16), sizes,
                                   gm.Tiles(16, 128), interpret=True)
    np.testing.assert_allclose(np.asarray(got[:40, :24]),
                               np.asarray(want[:40]), rtol=2e-6, atol=2e-5)
    assert not np.asarray(got[:40, 24:]).any()


def test_the_tile_rule_serves_the_published_width_through_its_lanes():
    cfg = nemotron_h.NemotronConfig.from_dict(nemotrongen.backbone(REAL))
    h, i = cfg.hidden_size, cfg.moe_intermediate_size
    assert (h, i, cfg.held, cfg.num_experts) == (2688, 1856, (0, 64), 128)
    rows = 256 * cfg.num_experts_per_tok
    # as published the lanes do not divide it: XLA's product would stay
    assert gm.tile_rule(rows, 64, h, i, jnp.bfloat16) is None
    assert gm.tile_rule(rows, 64, i, h, jnp.bfloat16) is None
    # as laid out both products are the kernel's, a whole matrix a block
    assert gm.tile_rule(rows, 64, h, gm.lanes(i), jnp.bfloat16) == gm.Tiles(
        128, 1920)
    assert gm.tile_rule(rows, 64, gm.lanes(i), h, jnp.bfloat16) == gm.Tiles(
        128, 2688)
    # every prefill bucket the cell's prompts fall in too
    for t in (96, 128, 192):
        assert gm.tile_rule(t * 6, 64, h, 1920, jnp.bfloat16) is not None


def test_the_new_field_leaves_the_sibling_programs_as_they_were():
    """``expert_act`` is read while a program is traced: an ``lfm2_moe`` or
    ``sdar_moe`` configuration states none and gets the graph it had."""
    tiny = json.loads((DATA / "lfm2-tiny.json").read_text())
    cfg = lfm2.Lfm2Config.from_dict(lfm2gen.backbone(tiny))
    assert cfg.expert_act == "swiglu"
    assert sdar.SdarConfig.from_dict(sdargen.backbone(json.loads(
        (DATA / "sdar-tiny.json").read_text()))).expert_act == "swiglu"
    params = {"embed": jnp.zeros((512, 64)), "norm_f": jnp.ones((64,)),
              "layers": [lfm2.pack_layer(wide(lfm2gen.draw_layer(tiny, i)))
                         for i in range(6)]}

    def graph(cfg):
        return str(jax.make_jaxpr(lambda p, c: lfm2.step(
            p, c, jnp.ones((2,), bool), jnp.zeros((2,)), 0, cfg=cfg,
            units=UNITS))(params, lfm2.new_cache(cfg, 2, 16)))

    plain = graph(cfg)
    assert plain == graph(dataclasses.replace(cfg, expert_act="swiglu"))
    # no shared expert, no square, a load of three numbers a layer
    assert "integer_pow" not in plain and "square" not in plain
    assert "i32[4,3]" in plain and "i32[4,5]" not in plain


# -- the configuration -------------------------------------------------------

def test_the_configuration_is_read_as_the_module_says():
    cfg = nemotron_h.NemotronConfig.from_dict(nemotrongen.backbone(REAL))
    assert cfg.pattern == "MEMEM*EME" and cfg.expert_layers == [1, 3, 6, 8]
    assert (cfg.d_inner, cfg.conv_dim, cfg.chunk_size) == (4096, 6144, 128)
    # a slot: 4 x (64 x 64 x 128 + 3 x 6144) float32, whatever its row holds
    assert cfg.ssm_state_bytes == 4 * 4 * (64 * 64 * 128 + 3 * 6144) \
        == 8683520
    assert (cfg.norm_eps, cfg.routed_scaling_factor, cfg.expert_act,
            cfg.router_scoring, cfg.tie_word_embeddings) == (
        1e-5, 2.5, "relu2", "sigmoid", False)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h.NemotronConfig.from_dict(dict(BB, num_hidden_layers=6))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h.NemotronConfig.from_dict(dict(
            BB, hybrid_override_pattern="MEM-EME"))
    with pytest.raises(ValueError, match="held"):
        nemotron_h.NemotronConfig.from_dict(dict(BB, n_routed_experts=3))
    with pytest.raises(ValueError, match="held"):
        nemotron_h.NemotronConfig.from_dict(dict(
            BB, expert_parallel={"routed_experts": 8, "held": [6, 4]}))
    with pytest.raises(ValueError, match="relu2"):
        nemotron_h.NemotronConfig.from_dict(dict(BB, mlp_hidden_act="silu"))
    whole = nemotron_h.NemotronConfig.from_dict(
        {k: v for k, v in BB.items() if k != "expert_parallel"})
    assert (whole.num_experts, whole.held) == (4, (0, 4))


# -- the voice and what its loop records ------------------------------------

def test_the_voice_runs_and_its_loop_says_what_the_state_and_the_share_cost(
        tmp_path, monkeypatch):
    monkeypatch.undo()          # the voice as served: bfloat16 products
    monkeypatch.setenv("SONATA_AR_SLOTS", "3")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "256")
    voice = from_config_path(nemotrongen.write_tensors(tmp_path, CONFIG))
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    tracer = tracing.default_tracer()
    tracer.clear()
    per_slot = 4 * 3 * (8 * 8 * 16 + 3 * 128)
    resident = stats.resident["sonata_ssm_state_resident_bytes"]
    try:
        assert type(voice.backbone).__name__ == "NemotronBackbone"
        described = voice.description
        assert (described.static, described.resident, voice.expert_layers,
                voice.expert_matmul) == (
            {"ssm_layers": 3, "latent_layers": 0},
            {"sonata_ssm_state_resident_bytes": 3 * per_slot}, [1, 4, 6],
            "ragged_dot")
        assert described.closed({"live_slot_steps": 5, "kv_positions": 9}) \
            == {"ssm_state_bytes": 2 * per_slot * 5, "latent_cache_bytes": 0}
        assert described.prefill(32) == {"ssm_chunks": 3 * 4}
        assert ("step_admit", 32) in voice.lattice_shapes("full")
        with tracer.trace_request("test", request_id="row-0"):
            audio = voice.speak_batch(
                list(voice.phonemize_text("one short row.")))
        ids = voice.config.phonemes_to_ids(
            list(voice.phonemize_text("one short row."))[0])
        assert len(audio[0].samples) == 16 * round(3.5 * len(ids))
        assert stats.resident["sonata_ssm_state_resident_bytes"] \
            == resident + 3 * per_slot
        assert f"sonata_ssm_state_resident_bytes {resident + 3 * per_slot}\n" \
            in registry.render()
    finally:
        voice.close()
    assert stats.resident["sonata_ssm_state_resident_bytes"] == resident
    traces = {t.request_id: t for t in tracer.recent_traces()}
    (prefill,) = [s.attrs for s in traces["row-0"].spans_snapshot()
                  if s.attrs.get("kind") == "prefill"]
    assert prefill["ssm_chunks"] == 3 * -(-prefill["text_bucket"] // 8)
    # the row's prompt rode the loop's first launch, which had no live row
    assert (prefill["admit"], prefill["step_no"]) == ("step", 0)
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups
    for g in groups:
        assert g["ssm_layers"] == 3 and g["layers"] == [1, 4, 6]
        assert g["ssm_state_bytes"] == 2 * per_slot * g["live_slot_steps"]
        assert g["assignments"] == [2 * (
            g["live_slot_steps"] - g["admit_steps"]
            + g["prompt_tokens"])] * 3
        assert all(0 <= h <= a for h, a in zip(g["held_assignments"],
                                               g["assignments"]))
        assert all(t <= 4 * g["steps"] for t in g["held_experts_touched"])
    assert 0 < sum(sum(g["held_assignments"]) for g in groups) < sum(
        sum(g["assignments"]) for g in groups)
    text = registry.render()
    assert 'sonata_moe_held_assignments_total{layer="4"}' in text


def test_the_warm_up_holds_as_many_caches_as_the_device_has_room_for(
        tmp_path, monkeypatch):
    """Every shape of the lattice warms (the vocoder without a cache of
    state), and the count of warm-up dispatches that may hold a cache at
    once follows the device's free memory: a cache of recurrent state is
    gigabytes at 256 slots, and four beside the weights do not fit."""
    monkeypatch.undo()
    monkeypatch.setenv("SONATA_AR_SLOTS", "3")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "64")
    voice = from_config_path(nemotrongen.write_tensors(tmp_path, CONFIG))
    try:
        need = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(
                       jax.eval_shape(voice.new_cache)))
        assert need > voice.description.resident[
            "sonata_ssm_state_resident_bytes"] > 0

        class Device:
            def __init__(self, stats):
                self.memory_stats = lambda: stats

        for stats, want in ((None, 1 << 30),
                            ({"bytes_limit": 10 * need + 5,
                              "bytes_in_use": 6 * need}, 3),
                            ({"bytes_limit": 10 * need,
                              "bytes_in_use": 9 * need}, 1)):
            voice._warm_caches = None
            monkeypatch.setattr(jax, "local_devices",
                                lambda stats=stats: [Device(stats)])
            assert voice._warm_cache_slots()._value == want
        shapes = voice.lattice_shapes("full")
        assert {s[0] for s in shapes} == {"step", "step_admit", "vocode"}
        for shape in shapes:
            voice.warm_shape(shape)
        assert voice._warm_caches._value == 1
        # warm: the same launch again compiles nothing (the listener's word)
        out, launch = voice.vocode(voice.new_cache(), 0, 1, shapes[-1][1])
        jax.block_until_ready(out)
        assert launch["frames_bucket"] == shapes[-1][1]
        assert launch["compile"] == "cached" and "compile_ms" not in launch
    finally:
        voice.close()
