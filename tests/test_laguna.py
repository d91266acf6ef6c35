"""The Laguna backbone (``sonata_tpu/models/laguna.py``) against the plain
reference (``perfbench/reference/laguna_ref.py``) at a tiny size on the CPU,
float32, seeded, with a window of 8 places (and of 32) and rows of 40 and
more positions, so that the ring wraps several times: prefill then steps
through the two kinds of cache against the reference's full pass (logits,
routes, every layer's cached rows), a prompt longer than the window, a slot
another row wrapped, YaRN's numbers worked by hand, both head counts, each
of the reference's faults, the shares of an expert layer against the uncut
layer, the configuration, and the voice with what its loop records."""

import dataclasses
import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import lagunagen, parts
from sonata_tpu.models import from_config_path, laguna, unit_backbone, \
    unit_layers, unit_voice
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.models.unit_backbone import routes_of
from sonata_tpu.ops import slot_attention
from sonata_tpu.serving import tracing
from sonata_tpu.serving.metrics import MetricsRegistry
from tests.voices import row_sums

ROOT = Path(__file__).resolve().parent.parent
DATA = ROOT / "tests/perfbench/data"
CONFIG = json.loads((DATA / "laguna-tiny.json").read_text())
REAL = json.loads((ROOT / "perfbench/configs/laguna/laguna-xs.2.json")
                  .read_text())
BB = lagunagen.backbone(CONFIG)
CFG = laguna.LagunaConfig.from_dict(BB)
UNITS = unit_layers.UnitIds(256, 511)
LAYERS = len(CFG.layer_types)
#: name -> (prompt ids, text bucket, window): shorter than the window's
#: bucket, a bucket the window holds, prompts longer than the window
PROMPTS = {"short": (5, 16, 8), "whole_bucket": (16, 16, 8),
           "longer": (19, 32, 8), "inside_the_window": (5, 16, 32),
           "the_window_whole": (19, 32, 32)}
STEPS = 40
ref = parts.load_file(ROOT / "perfbench/reference/laguna_ref.py")


def wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def with_window(window: int):
    """The tiny configuration with another window: the program's, the
    reference's."""
    return (dataclasses.replace(CFG, sliding_window=window),
            dict(BB, sliding_window=window))


@pytest.fixture(autouse=True)
def float32_products(monkeypatch):
    """The program's products take bfloat16 inputs; here they take float32
    at ``highest``, so that it can be held to the reference to rounding."""
    monkeypatch.setattr(unit_layers, "BF16", jnp.float32)
    monkeypatch.setattr(laguna, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def raw():
    return [wide(lagunagen.draw_layer(CONFIG, i)) for i in range(LAYERS)]


@pytest.fixture(scope="module")
def params(raw):
    return {"embed": wide(lagunagen.draw(CONFIG, "embed")),
            "head": wide(lagunagen.draw(CONFIG, "head")),
            "norm_f": wide(lagunagen.draw(CONFIG, "norm_f")),
            "layers": [laguna.pack_layer(r) for r in raw]}


def prompt(n: int, bucket: int, seed: int = 0):
    ids = np.random.default_rng(seed + n).integers(0, 256, size=(n,))
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = ids
    return ids.tolist(), jnp.asarray(padded)


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """``prefill`` and ``step`` of ``cfg``, jitted (under the fixture's
    float32 products: a trace reads ``BF16`` when it is made)."""
    return (jax.jit(functools.partial(laguna.prefill, cfg=cfg, units=UNITS)),
            jax.jit(functools.partial(laguna.step, cfg=cfg, units=UNITS)))


def run_row(params, cache, slot: int, ids: list, padded, steps: int,
            cfg=CFG):
    """Prefill into ``slot`` and ``steps`` greedy steps of that slot alone:
    the logits of every launch, the tokens fed, and the cache."""
    prefill, step = programs(cfg)
    cache, logits, _ = prefill(
        params, cache, padded, jnp.int32(len(ids)), jnp.int32(slot),
        jnp.float32(0.0), jax.random.PRNGKey(0))
    live = jnp.arange(cache["pos"].shape[0]) == slot
    got, tokens = [np.asarray(logits)], list(ids)
    for k in range(steps):
        tokens.append(int(cache["token"][slot]))
        cache, out, _ = step(params, cache, live, jnp.zeros(live.shape), k)
        got.append(np.asarray(out[slot]))
    return np.stack(got), tokens, cache


def reference(tokens, params, raw, faults=None, bb=BB):
    return ref.forward(jnp.asarray(tokens), bb, params["embed"],
                       params["head"], params["norm_f"], lambda i: raw[i],
                       held=CFG.held, faults=faults)


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_prefill_then_steps_give_the_references_full_pass(name, raw, params):
    """Logits of the prefill and of 40 steps through the slot's whole
    caches and its rings against one whole pass of the reference (no cache,
    the window a mask) over prompt + units; the experts chosen too; and
    every layer's cached rows are the reference's keys and values: a ring's
    place ``p`` holds the latest position congruent to ``p``."""
    n, bucket, window = PROMPTS[name]
    cfg, bb = with_window(window)
    ids, padded = prompt(n, bucket)
    got, tokens, cache = run_row(params, laguna.new_cache(cfg, 3, 64), 1,
                                 ids, padded, STEPS, cfg)
    assert len(tokens) >= 40 + n > 5 * 8
    want, routes = reference(tokens, params, raw, bb=bb)
    np.testing.assert_allclose(got, np.asarray(want)[n - 1:], rtol=0,
                               atol=2e-4)
    served = routes_of(cfg, np.asarray(cache["routes"][1]))[:len(tokens)]
    assert served.dtype == np.uint8 and served.shape[1:] == (4, 2)
    assert np.array_equal(np.sort(served, -1), np.sort(np.asarray(routes),
                                                       -1))
    h = params["embed"][jnp.asarray(tokens)]
    t = len(tokens)
    for i, kind in enumerate(cfg.layer_types):
        keys, values = ref.left(h, raw[i], i, bb)
        for name_, rows in (("k", keys), ("v", values)):
            held = slot_attention.read_slot(np.asarray(cache[name_][i][1]),
                                            2, 16)
            rows = np.asarray(rows)
            if kind == laguna.FULL:
                assert held.shape[0] == 64
                np.testing.assert_allclose(held[:t], rows, rtol=0, atol=2e-5)
                continue
            assert held.shape[0] == window
            latest = [max(p for p in range(t) if p % window == place)
                      for place in range(window)]
            np.testing.assert_allclose(held, rows[latest], rtol=0, atol=2e-5)
        h, _ = ref.layer(h, raw[i], i, bb, CFG.held)


def test_a_slot_another_row_wrapped_gives_what_a_fresh_slot_gives(params):
    """A ring's places are read only as far as the row has written them:
    the second row of a slot does not see what the first one, which wrapped
    the ring five times, left there.  To the bit."""
    first, first_padded = prompt(19, 32)
    second, second_padded = prompt(5, 16, seed=7)
    _, tokens, used = run_row(params, laguna.new_cache(CFG, 2, 64), 1, first,
                              first_padded, 25)
    assert len(tokens) > 5 * CFG.sliding_window
    again, tokens, _ = run_row(params, used, 1, second, second_padded, 12)
    fresh, fresh_tokens, _ = run_row(
        params, laguna.new_cache(CFG, 2, 64), 1, second, second_padded, 12)
    assert tokens == fresh_tokens and np.array_equal(again, fresh)


FAULTS = {"no_window": {"window": False},
          "sliding_rule_in_full_layers": {"full_rule": laguna.SLIDING},
          "no_yarn_factor": {"yarn_factor": False},
          "no_gate": {"gate": False}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_of_the_reference_is_far_from_the_program(fault, raw,
                                                             params):
    """What the comparison's controls plant moves the logits by far more
    than the program differs from the sound reference."""
    ids, padded = prompt(9, 16)
    got, tokens, _ = run_row(params, laguna.new_cache(CFG, 2, 64), 0, ids,
                             padded, 20)
    want = np.asarray(reference(tokens, params, raw)[0])[len(ids) - 1:]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    faulty = np.asarray(reference(tokens, params, raw, FAULTS[fault])[0])[
        len(ids) - 1:]
    assert np.abs(faulty - got).max() > 0.05 * float(np.std(want))


def test_a_stale_ring_shows_below_the_window_alone(raw, params):
    """The reference's ``stale`` fault (a reused slot's old places left
    readable) moves a sliding layer's result at positions below the window
    and at none from it on."""
    cfg, bb = with_window(16)
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((40, CFG.hidden_size)), jnp.float32)
    other = jnp.asarray(rng.standard_normal((40, CFG.hidden_size)),
                        jnp.float32)
    p = raw[1]["attn"]
    sound = ref.attention(u, p, laguna.SLIDING, 6, bb)
    keys, values = ref.keys_values(other, p, laguna.SLIDING, bb)
    stale = ref.attention(u, p, laguna.SLIDING, 6, bb,
                          {"stale": (keys, values, 37)})
    moved = np.abs(np.asarray(stale - sound)).max(-1)
    assert (moved[:15] > 1e-3).all() and (moved[15:] < 1e-6).all()
    # what the old row left: at place j its latest position congruent to j
    old_k, _ = ref.ring_left(keys, values, 37, 16)
    at = [max(p_ for p_ in range(37) if p_ % 16 == j) for j in range(16)]
    assert np.array_equal(np.asarray(old_k), np.asarray(keys)[at])


# -- the two rotary rules --------------------------------------------------

def test_yarn_s_paces_and_factor_are_the_numbers_worked_by_hand():
    """The published rule of the full layers: 64 rotated dimensions, theta
    500 000, factor 64, 4096 original positions, beta 64 and 1.  A rotation
    makes 64 turns in 4096 positions at dimension 64 ln(4096 / (2 pi 64)) /
    (2 ln 500 000) = 5.66 and one turn at 15.80: low 5, high 16."""
    cfg = laguna.LagunaConfig.from_dict(lagunagen.backbone(REAL))
    rule = cfg.rotary_rule(laguna.FULL)
    assert (rule.dims, len(rule.inv_freq)) == (64, 32)
    assert rule.factor == pytest.approx(1.4158883083359672)
    assert rule.factor == pytest.approx(0.1 * math.log(64) + 1)
    assert 64 * math.log(4096 / (2 * math.pi * 64)) / (
        2 * math.log(5e5)) == pytest.approx(5.66, abs=0.01)
    assert 64 * math.log(4096 / (2 * math.pi)) / (
        2 * math.log(5e5)) == pytest.approx(15.80, abs=0.01)
    base = [5e5 ** (-2 * i / 64) for i in range(32)]
    inv = np.asarray(rule.inv_freq)
    # frequencies 0-5 keep their pace, 16-31 run at a sixty-fourth of it
    np.testing.assert_allclose(inv[:6], base[:6], rtol=1e-12)
    np.testing.assert_allclose(inv[16:], np.asarray(base[16:]) / 64,
                               rtol=1e-12)
    # the ramp between: at i = 10, (10 - 5) / 11 of the way
    ramp = 5 / 11
    assert inv[10] == pytest.approx(
        (1 - ramp) * base[10] + ramp * base[10] / 64, rel=1e-12)
    # base_10 = exp(-20 / 64 x 13.12236) = 0.0165603; 6 / 11 of it and
    # 5 / 11 of a sixty-fourth of it: 0.0090329 + 0.0001176
    assert inv[10] == pytest.approx(0.0091506, rel=1e-4)
    # the reference works the same numbers by its own code
    np.testing.assert_allclose(
        ref.inv_freq(REAL["rope_parameters"][laguna.FULL], 64), inv,
        rtol=1e-12)
    # the sliding layers: plain, every dimension, no factor
    plain = cfg.rotary_rule(laguna.SLIDING)
    assert (plain.dims, plain.factor) == (128, 1.0)
    np.testing.assert_allclose(
        plain.inv_freq, [1e4 ** (-2 * i / 128) for i in range(64)],
        rtol=1e-12)


def test_a_full_layer_rotates_half_a_head_and_scales_it():
    """Position 0 turns nothing: the rotated half comes back times the
    factor, the other half as it went in, at every position."""
    rule = laguna.rotary_of(REAL["rope_parameters"][laguna.FULL], 128)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 2, 128)),
                    jnp.float32)
    out = np.asarray(laguna.rotate(x, jnp.asarray([0, 7, 900]), rule))
    np.testing.assert_allclose(out[0, :, :64], np.asarray(x)[0, :, :64]
                               * rule.factor, rtol=1e-6)
    assert np.array_equal(out[..., 64:], np.asarray(x)[..., 64:])
    # a rotation keeps a pair's length, times the factor
    for i in (0, 31):
        np.testing.assert_allclose(
            np.hypot(out[2, :, i], out[2, :, 32 + i]),
            np.hypot(x[2, :, i], x[2, :, 32 + i]) * rule.factor, rtol=1e-5)


# -- both head counts --------------------------------------------------------

@pytest.mark.parametrize("i", [0, 1], ids=["full-4-heads", "sliding-6-heads"])
def test_a_layers_attention_is_the_references_at_its_own_head_count(i, raw):
    """One layer's attention over 21 positions (a window of 8): the prompt's
    einsum whole against a position at a time through the layer's cache (a
    ring in the sliding layer), and both against the reference."""
    p = laguna.pack_layer(raw[i])["attn"]
    heads = CFG.heads_per_layer[i]
    assert p["wqkvg"].shape == (64, (heads + 4) * 16 + heads)
    t = 21
    u = jnp.asarray(np.random.default_rng(3).standard_normal(
        (t, CFG.hidden_size)), jnp.float32)
    q, k, v, gate = laguna.qkvg(u, p, CFG, i, jnp.arange(t))
    assert q.shape == (t, heads, 16) and gate.shape == (t, heads)
    whole = laguna.attend_seq(q, k, v, CFG, i)
    places = CFG.places(CFG.layer_types[i], 32)
    assert places == (32 if i == 0 else 8)
    k_buf = v_buf = jnp.zeros(slot_attention.stored_shape(1, places, 2, 16))
    for at in range(t):
        one, k_buf, v_buf = laguna.attend_step(
            q[at:at + 1], k[at:at + 1], v[at:at + 1], k_buf, v_buf,
            jnp.asarray([at]), CFG, i)
        np.testing.assert_allclose(np.asarray(one[0]), np.asarray(whole[at]),
                                   rtol=0, atol=2e-5)
    want = ref.attention(u, raw[i]["attn"], CFG.layer_types[i], heads, BB)
    got = (whole * gate[:, :, None]).reshape(t, -1) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-5)


# -- the shares --------------------------------------------------------------

def uncut(raw_ffn):
    """A layer's experts with all 8 routed ones: the held two, and six more
    drawn for the other three chips."""
    rng = np.random.default_rng(11)
    out = dict(raw_ffn)
    for k in ("w1", "w3", "w2"):
        more = rng.uniform(-1, 1, (6,) + raw_ffn[k].shape[1:]) * float(
            jnp.abs(raw_ffn[k]).max())
        out[k] = jnp.concatenate([raw_ffn[k],
                                  jnp.asarray(more, jnp.float32)])
    return out


def test_the_shares_add_up_to_the_uncut_layer_the_shared_expert_once(raw):
    """``held = (0, 2)``, ``(2, 2)``, ``(4, 2)`` and ``(6, 2)`` of 8 (the
    cell: eight shares of 32 of 256), what every chip computes alike (the
    shared expert) counted once, against the reference's whole layer; each
    share against the reference's share."""
    whole = uncut(raw[1]["ffn"])
    u = jnp.asarray(np.random.default_rng(5).standard_normal(
        (13, CFG.hidden_size)), jnp.float32)
    want, chosen = ref.experts(u, whole, BB)
    shared = ref.swiglu(u, whole["shared_w1"], whole["shared_w3"],
                        whole["shared_w2"])
    total = -3 * shared
    for first in (0, 2, 4, 6):
        share = dict(whole, **{k: whole[k][first:first + 2]
                               for k in ("w1", "w3", "w2")})
        packed = laguna.pack_layer(dict(raw[1], ffn=share))["ffn"]
        got, took, load = unit_layers.moe_ffn(u, packed, CFG, (first, 2))
        part, _ = ref.experts(u, share, BB, held=(first, 2))
        np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                   rtol=0, atol=2e-5)
        assert np.array_equal(np.sort(np.asarray(took), -1),
                              np.sort(np.asarray(chosen), -1))
        total = total + got
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=5e-5)


# -- the configuration -------------------------------------------------------

def test_the_configuration_is_read_as_the_module_says():
    cfg = laguna.LagunaConfig.from_dict(lagunagen.backbone(REAL))
    assert cfg.layer_types == (laguna.FULL,) + (laguna.SLIDING,) * 3 + (
        laguna.FULL,) + (laguna.SLIDING,) * 3
    assert cfg.heads_per_layer == (48, 64, 64, 64, 48, 64, 64, 64)
    assert cfg.expert_layers == [1, 2, 3, 4, 5, 6, 7]
    assert (cfg.num_experts, cfg.held, cfg.num_experts_per_tok,
            cfg.vocab_size) == (256, (0, 32), 8, 100352)
    assert (cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window,
            cfg.hidden_size, cfg.moe_intermediate_size) == (
        8, 128, 512, 2048, 512)
    assert (cfg.norm_eps, cfg.routed_scaling_factor, cfg.expert_act,
            cfg.router_scoring, cfg.use_expert_bias, cfg.norm_topk_prob,
            cfg.tie_word_embeddings) == (1e-6, 2.5, "swiglu", "sigmoid",
                                         False, True, False)
    # a place: 8 heads of 128, keys and values, bfloat16
    assert cfg.place_bytes == 4096
    assert (cfg.places(laguna.FULL, 1024), cfg.places(laguna.SLIDING, 1024),
            cfg.places(laguna.SLIDING, 300)) == (1024, 512, 300)
    # the thin path at an eighth: 2048 assignments bounded to 640 rows
    assert unit_layers.held_rows(cfg, 256, cfg.held) == 640
    # both geometries have chunks of 128 places: both run the kernel
    for places, g in ((1024, 6), (512, 8)):
        assert slot_attention.tile_rule(places, 8, g, 128, 1) == \
            slot_attention.Tiles(128)
    cache = jax.eval_shape(lambda: laguna.new_cache(cfg, 256, 1024))
    assert [a.shape for a in cache["k"]] == [
        (256, 1024 if kind == laguna.FULL else 512, 1024)
        for kind in cfg.layer_types]
    assert cache["routes"].shape == (256, 1024, 128)
    with pytest.raises(ValueError, match="known layers"):
        laguna.LagunaConfig.from_dict(dict(BB, num_hidden_layers=9))
    with pytest.raises(ValueError, match="known layers"):
        laguna.LagunaConfig.from_dict(dict(BB, layer_types=["conv"] * 5))
    with pytest.raises(ValueError, match="do not divide"):
        laguna.LagunaConfig.from_dict(dict(
            BB, num_attention_heads_per_layer=[4, 5, 6, 6, 4]))
    with pytest.raises(ValueError, match="gated"):
        laguna.LagunaConfig.from_dict(dict(BB, gating=False))
    with pytest.raises(ValueError, match="held"):
        laguna.LagunaConfig.from_dict(dict(BB, num_experts=3))
    with pytest.raises(ValueError, match="rope_type"):
        laguna.rotary_of({"rope_type": "linear", "rope_theta": 1e4}, 16)
    whole = laguna.LagunaConfig.from_dict(
        {k: v for k, v in BB.items() if k != "expert_parallel"})
    assert (whole.num_experts, whole.held) == (2, (0, 2))


def test_the_cells_reader_is_counted_in_chunks_and_a_ring_at_its_window(
        monkeypatch):
    """``kv_places_fetched`` at the cell's size where the kernel reads (the
    rule steered as on a TPU): two whole caches and six rings of 512
    places in chunks of 128, a ring read no further than its window; of
    what is moved the rows hold ``kv_cache_bytes`` over a place's bytes."""
    monkeypatch.setattr(slot_attention, "_tiles_here",
                        slot_attention.tile_rule)
    backbone = unit_voice.make_backbone(lagunagen.backbone(REAL),
                                        REAL["voice"]["units"])
    reaches = backbone.kv_reaches(1024)
    assert sorted(reaches) == [(512, 128, 6), (1024, 128, 2)]
    counted = functools.partial(unit_backbone.places_fetched, reaches)
    assert [counted(n) for n in (0, 1, 128, 129, 512, 513, 819, 1024)] == [
        0, 8 * 128, 8 * 128, 8 * 256, 8 * 512, 2 * 640 + 6 * 512,
        2 * 896 + 6 * 512, 2 * 1024 + 6 * 512]
    described = backbone.describe(256, 1024)
    for n in (1, 347, 513, 1024):
        held = row_sums(described, n)["kv_cache_bytes"] \
            // backbone.cfg.place_bytes
        assert held == 2 * n + 6 * min(n, 512) and 0 < held <= counted(n)
    # the einsum, off a TPU, reads every place of every buffer
    monkeypatch.undo()
    assert sorted(backbone.kv_reaches(1024)) == [(512, 512, 6),
                                                 (1024, 1024, 2)]


# -- the voice and what its loop records ------------------------------------

def test_the_voice_runs_and_its_loop_says_what_the_two_caches_cost(
        tmp_path, monkeypatch):
    monkeypatch.undo()          # the voice as served: bfloat16 products
    monkeypatch.setenv("SONATA_AR_SLOTS", "3")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "256")
    voice = from_config_path(lagunagen.write_tensors(tmp_path, CONFIG))
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    tracer = tracing.default_tracer()
    tracer.clear()
    place = 2 * 2 * 128                 # 2 heads of 16 in 128 lanes, k and v
    series = 'sonata_attn_cache_resident_bytes{kind="%s"}'
    before = {kind: stats.resident[series % kind]
              for kind in ("full", "ring")}
    bound_before = stats.window_bound_row_steps
    try:
        assert type(voice.backbone).__name__ == "LagunaBackbone"
        described = voice.description
        assert (described.static, voice.attention, voice.expert_layers,
                voice.expert_matmul) == (
            {"ssm_layers": 0, "latent_layers": 0, "full_layers": 2,
             "window_layers": 3, "window": 8}, "einsum", [1, 2, 3, 4],
            "ragged_dot")
        assert row_sums(described, 5)["kv_cache_bytes"] == place * 5 * 5
        assert row_sums(described, 30)["kv_cache_bytes"] == place * (
            2 * 30 + 3 * 8)
        # off a TPU the einsum moves every place of a layer's buffer: a
        # whole cache's 256, a ring's 8
        assert [row_sums(described, n)["kv_places_fetched"]
                for n in (0, 5, 30)] == [0, 2 * 256 + 3 * 8, 2 * 256 + 3 * 8]
        # a row's position is the last it attends over: bound from 9 on
        assert [row_sums(described, n)["window_bound_row_steps"]
                for n in (8, 9)] == [0, 1]
        assert described.resident == {
            series % "full": 3 * place * 2 * 256,
            series % "ring": 3 * place * 3 * 8}
        assert ("step_admit", 32) in voice.lattice_shapes("full")
        with tracer.trace_request("test", request_id="row-0"):
            audio = voice.speak_batch(
                list(voice.phonemize_text("one short row.")))
        ids = voice.config.phonemes_to_ids(
            list(voice.phonemize_text("one short row."))[0])
        assert len(audio[0].samples) == 16 * round(3.5 * len(ids))
        held = {"full": before["full"] + 3 * place * 2 * 256,
                "ring": before["ring"] + 3 * place * 3 * 8}
        assert {kind: stats.resident[series % kind] for kind in held} == held
        text = registry.render()
        for kind in ("full", "ring"):
            assert 'sonata_attn_cache_resident_bytes{kind="%s"} %d\n' % (
                kind, held[kind]) in text
    finally:
        voice.close()
    assert {kind: stats.resident[series % kind] for kind in before} == before
    traces = {t.request_id: t for t in tracer.recent_traces()}
    (prefill,) = [s.attrs for s in traces["row-0"].spans_snapshot()
                  if s.attrs.get("kind") == "prefill"]
    assert (prefill["admit"], prefill["step_no"],
            prefill["window_layers"]) == ("step", 0, 3)
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups
    for g in groups:
        assert (g["full_layers"], g["window_layers"], g["window"],
                g["latent_layers"], g["ssm_layers"]) == (2, 3, 8, 0, 0)
        assert g["attention"] == "einsum"
        stepped = g["live_slot_steps"] - g["admit_steps"]
        assert 0 <= g["window_bound_row_steps"] <= stepped
        # a full layer's places and at most the window's in a ring
        assert place * 2 * g["kv_positions"] < g["kv_cache_bytes"] <= \
            place * (2 * g["kv_positions"] + 3 * 8 * stepped)
        assert g["kv_places_fetched"] == (2 * 256 + 3 * 8) * stepped \
            >= g["kv_cache_bytes"] // place
    # one row of len(ids) + budget - 1 positions: every step from the one
    # at position 8 on is bound by the window
    budget = round(3.5 * len(ids))
    bound = sum(g["window_bound_row_steps"] for g in groups)
    assert bound == len(ids) + budget - 1 - max(len(ids), 8)
    assert stats.window_bound_row_steps == bound_before + bound
    assert f"sonata_attn_window_bound_row_steps_total " \
        f"{stats.window_bound_row_steps}\n" in registry.render()
    assert f"sonata_kv_places_fetched_total {stats.kv_places_fetched}\n" \
        in registry.render() and stats.kv_places_fetched >= sum(
            g["kv_places_fetched"] for g in groups) > 0
