"""The openPangu-Ultra-MoE backbone (``sonata_tpu/models/pangu_moe.py``)
against the plain reference (``perfbench/reference/pangu_ref.py``) at a tiny
size on the CPU, float32, seeded: prefill then steps through the latent
cache against the reference's full pass, the absorbed form of latent
attention against the per-head form, a slot reused, the 4 shares of an
expert layer against the uncut layer and the 8 slices of the vocabulary
against the whole head, each of the four norms left out, a thin share's
short path and its overflow, the configuration, and the voice with what its
loop records."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import pangugen, parts
from sonata_tpu.models import from_config_path, pangu_moe, unit_layers
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.models.unit_backbone import routes_of
from sonata_tpu.ops import slot_attention
from sonata_tpu.serving import tracing
from sonata_tpu.serving.metrics import MetricsRegistry
from tests.voices import row_sums

ROOT = Path(__file__).resolve().parent.parent
DATA = ROOT / "tests/perfbench/data"
CONFIG = json.loads((DATA / "pangu-tiny.json").read_text())
REAL = json.loads((ROOT / "perfbench/configs/pangu/"
                   "openpangu-ultra-moe-718b.json").read_text())
BB = pangugen.backbone(CONFIG)
CFG = pangu_moe.PanguConfig.from_dict(BB)
UNITS = unit_layers.UnitIds(256, 511)
LAYERS = CFG.num_hidden_layers
PROMPTS = {"short": (5, 16), "whole_bucket": (16, 16), "longer": (19, 32)}
NORMS = ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")
ref = parts.load_file(ROOT / "perfbench/reference/pangu_ref.py")


def wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(autouse=True)
def float32_products(monkeypatch):
    """The program's products take bfloat16 inputs; here they take float32
    at ``highest``, so that it can be held to the reference to rounding."""
    monkeypatch.setattr(unit_layers, "BF16", jnp.float32)
    monkeypatch.setattr(pangu_moe, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def raw():
    return [wide(pangugen.draw_layer(CONFIG, i)) for i in range(LAYERS)]


@pytest.fixture(scope="module")
def params(raw):
    return {"embed": wide(pangugen.draw(CONFIG, "embed")),
            "head": wide(pangugen.draw(CONFIG, "head")),
            "norm_f": wide(pangugen.draw(CONFIG, "norm_f")),
            "layers": [pangu_moe.pack_layer(r, CFG) for r in raw]}


def prompt(n: int, bucket: int, seed: int = 0):
    ids = np.random.default_rng(seed + n).integers(0, 256, size=(n,))
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = ids
    return ids.tolist(), jnp.asarray(padded)


def run_row(params, cache, slot: int, ids: list, padded, steps: int,
            cfg=CFG):
    """Prefill into ``slot`` and ``steps`` greedy steps of that slot alone:
    the logits of every launch, the tokens fed, and the cache."""
    cache, logits, _ = pangu_moe.prefill(
        params, cache, padded, jnp.int32(len(ids)), jnp.int32(slot),
        jnp.float32(0.0), jax.random.PRNGKey(0), cfg=cfg, units=UNITS)
    live = jnp.arange(cache["pos"].shape[0]) == slot
    got, tokens = [np.asarray(logits)], list(ids)
    for k in range(steps):
        tokens.append(int(cache["token"][slot]))
        cache, out, _ = pangu_moe.step(
            params, cache, live, jnp.zeros(live.shape), k, cfg=cfg,
            units=UNITS)
        got.append(np.asarray(out[slot]))
    return np.stack(got), tokens, cache


def reference(tokens, params, raw, faults=None):
    return ref.forward(jnp.asarray(tokens), BB, params["embed"],
                       params["head"], params["norm_f"], lambda i: raw[i],
                       held=CFG.held, faults=faults)


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_prefill_then_steps_give_the_references_full_pass(name, raw, params):
    """Logits of the prefill (expanded form) and of 12 steps through the
    slot's latent rows (absorbed form) against one whole pass of the
    reference (per head, no cache) over prompt + units; the experts chosen
    too."""
    n, bucket = PROMPTS[name]
    ids, padded = prompt(n, bucket)
    got, tokens, cache = run_row(params, pangu_moe.new_cache(CFG, 3, 64), 1,
                                 ids, padded, 12)
    want, routes = reference(tokens, params, raw)
    np.testing.assert_allclose(got, np.asarray(want)[n - 1:], rtol=0,
                               atol=2e-4)
    served = routes_of(CFG, np.asarray(cache["routes"][1]))[:len(tokens)]
    assert served.dtype == np.uint8 and served.shape[1:] == (3, 2)
    assert np.array_equal(np.sort(served, -1), np.sort(np.asarray(routes),
                                                       -1))
    # the cached rows are the reference's [c_kv | k_r], layer by layer
    h = params["embed"][jnp.asarray(tokens)]
    for i in range(LAYERS):
        rows = np.asarray(ref.left(h, raw[i], BB))
        held = np.asarray(cache["latent"][i][1, :len(tokens)])
        assert held.shape[1] == 128      # 40 values in whole lanes
        np.testing.assert_allclose(held[:, :CFG.latent_width], rows, rtol=0,
                                   atol=2e-5)
        assert not held[:, CFG.latent_width:].any()
        h, _ = ref.layer(h, raw[i], i < CFG.first_k_dense_replace, BB,
                         CFG.held)


def test_the_absorbed_form_is_the_per_head_form(raw):
    """One layer's attention over 9 positions: ``mla_seq`` whole against
    ``mla_step`` a position at a time through a cache, and both against the
    reference."""
    p = pangu_moe.pack_layer(raw[1], CFG)["attn"]
    t = 9
    u = jnp.asarray(np.random.default_rng(3).standard_normal(
        (t, CFG.hidden_size)), jnp.float32)
    q_nope, q_rope, row = pangu_moe.mla_in(u, p, CFG, jnp.arange(t))
    whole = pangu_moe.mla_seq(q_nope, q_rope, row, p, CFG)
    buf = jnp.zeros(slot_attention.stored_shape(1, 16, 1, CFG.latent_width))
    for k in range(t):
        buf = slot_attention.write_rows(buf, row[k:k + 1, None],
                                        jnp.asarray([[k]]))
        one = pangu_moe.mla_step(q_nope[k:k + 1], q_rope[k:k + 1], buf,
                                 jnp.asarray([k + 1]), p, CFG)
        np.testing.assert_allclose(np.asarray(one[0]), np.asarray(whole[k]),
                                   rtol=0, atol=2e-5)
    want = ref.attention(u, raw[1]["attn"], BB)
    np.testing.assert_allclose(np.asarray(whole @ p["wo"]), np.asarray(want),
                               rtol=0, atol=2e-5)


def test_a_slot_a_row_has_left_gives_what_a_fresh_slot_gives(params):
    """Latent rows are masked by position: the second row of a slot does
    not see the first one's.  To the bit."""
    first, first_padded = prompt(19, 32)
    second, second_padded = prompt(11, 16, seed=7)
    _, _, used = run_row(params, pangu_moe.new_cache(CFG, 2, 64), 1, first,
                         first_padded, 9)
    again, tokens, _ = run_row(params, used, 1, second, second_padded, 9)
    fresh, fresh_tokens, _ = run_row(
        params, pangu_moe.new_cache(CFG, 2, 64), 1, second, second_padded, 9)
    assert tokens == fresh_tokens and np.array_equal(again, fresh)


def test_an_empty_slot_costs_no_expert_product_and_does_not_move(params):
    cache = pangu_moe.new_cache(CFG, 3, 64)
    ids, padded = prompt(8, 16)
    _, _, cache = run_row(params, cache, 2, ids, padded, 0)
    before = {k: np.asarray(cache[k]) for k in ("token", "pos", "count")}
    cache, _, load = pangu_moe.step(
        params, cache, jnp.asarray([False, False, True]), jnp.zeros((3,)), 0,
        cfg=CFG, units=UNITS)
    load = np.asarray(load)
    assert load.shape == (3, 5) and load[:, 2].tolist() == [2, 2, 2]
    assert (load[:, 4] <= load[:, 2]).all() and (load[:, 3] <= 2).all()
    for k in ("token", "pos", "count"):
        assert np.array_equal(np.asarray(cache[k])[:2], before[k][:2])
    assert int(cache["pos"][2]) == before["pos"][2] + 1


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
    """``held = (0, 2)``, ``(2, 2)``, ``(4, 2)`` and ``(6, 2)`` of 8, what
    every chip computes alike (the shared expert) counted once, against the
    reference's whole layer; each share against the reference's share."""
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
        packed = pangu_moe.pack_layer(dict(raw[1], ffn=share), CFG)["ffn"]
        got, took, load = unit_layers.moe_ffn(u, packed, CFG, (first, 2))
        part, _ = ref.experts(u, share, BB, held=(first, 2))
        np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                   rtol=0, atol=2e-5)
        assert np.array_equal(np.sort(np.asarray(took), -1),
                              np.sort(np.asarray(chosen), -1))
        total = total + got
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0,
                               atol=5e-5)


def test_the_vocabulary_slices_side_by_side_are_the_whole_head(params):
    """Eight chips hold 64 rows each of a head of 512: their logits laid
    side by side are the whole head's, and this chip's are the first."""
    h = jnp.asarray(np.random.default_rng(8).standard_normal(
        (5, CFG.hidden_size)), jnp.float32)
    whole = unit_layers._head(h, params, CFG)
    slices = [unit_layers._head(
        h, dict(params, head=params["head"][k:k + 64]), CFG)
        for k in range(0, 512, 64)]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(slices, -1)),
                               np.asarray(whole), rtol=0, atol=1e-5)
    want = ref.head(h, params["head"][:64], params["norm_f"], BB)
    np.testing.assert_allclose(np.asarray(slices[0]), np.asarray(want),
                               rtol=0, atol=1e-5)


# -- the norms -----------------------------------------------------------------

@pytest.mark.parametrize("norm", NORMS)
def test_each_of_the_four_norms_left_out_fails(norm, raw, params):
    """A layer whose ``norm`` is the identity (gain 1, no division) is far
    from the reference, and so is one that wears a neighbour's gain."""
    ids, padded = prompt(9, 16)
    tokens = None

    def logits(layers):
        nonlocal tokens
        got, tokens, _ = run_row(dict(params, layers=layers),
                                 pangu_moe.new_cache(CFG, 2, 64), 0, ids,
                                 padded, 3)
        return got

    sound = logits(params["layers"])
    want, _ = reference(tokens, params, raw)
    want = np.asarray(want)[len(ids) - 1:]
    np.testing.assert_allclose(sound, want, rtol=0, atol=2e-4)
    spread = float(np.std(want))
    other = NORMS[(NORMS.index(norm) + 1) % 4]
    swapped = [dict(p, **{norm: p[other]}) for p in params["layers"]]
    assert np.abs(logits(swapped) - want).max() > 0.02 * spread
    # left out altogether: the reference says what that gives
    if norm == "post_attn_norm":
        dropped, _ = reference(tokens, params, raw, {"post_norm": False})
        assert np.abs(np.asarray(dropped)[len(ids) - 1:] - want).max() \
            > 0.05 * spread


# -- a thin share ----------------------------------------------------------------

def thin_layer(tokens: int, skew: float = 0.0, seed: int = 0):
    """An expert layer of 64 experts of which 2 are held, over ``tokens``
    tokens; ``skew`` tilts the router towards the held ones."""
    cfg = dataclasses.replace(CFG, num_experts=64, held=(6, 2))
    rng = np.random.default_rng(seed)
    h, m = cfg.hidden_size, cfg.moe_intermediate_size
    router = rng.standard_normal((h, 64)) / np.sqrt(h)
    u = rng.standard_normal((tokens, h))
    router[:, 6:8] += skew * u.mean(0)[:, None] + skew * np.abs(
        router[:, 6:8])
    raw = {"router": router, "w1": rng.standard_normal((2, h, m)) * 0.2,
           "w3": rng.standard_normal((2, h, m)) * 0.2,
           "w2": rng.standard_normal((2, m, h)) * 0.2,
           "shared_w1": rng.standard_normal((h, m)) * 0.2,
           "shared_w3": rng.standard_normal((h, m)) * 0.2,
           "shared_w2": rng.standard_normal((m, h)) * 0.2}
    raw = {k: jnp.asarray(v, jnp.float32) for k, v in raw.items()}
    packed = {"router": raw["router"],
              "w13": jnp.concatenate([raw["w1"], raw["w3"]], -1),
              "w2": raw["w2"],
              "shared": {"w_up": jnp.concatenate([raw["shared_w1"],
                                                  raw["shared_w3"]], -1),
                         "w_down": raw["shared_w2"]}}
    bb = dict(BB, expert_parallel={"routed_experts": 64, "held": [6, 2]})
    return cfg, jnp.asarray(u, jnp.float32), raw, packed, bb


@pytest.mark.parametrize("skew,overflows", [(0.0, False), (4.0, True)],
                         ids=["fits", "overflows"])
def test_a_thin_share_takes_the_short_path_and_leaves_nothing_out(
        skew, overflows):
    """2 of 64 experts held, 1024 tokens, 2 a token: 2048 assignments of
    which an even router gives the held 64, and the short path takes 192;
    a router tilted towards them gives them more, the same program takes
    its full-length path, and either way the result is the reference's
    share."""
    cfg, u, raw, packed, bb = thin_layer(1024, skew)
    assert unit_layers.held_rows(cfg, 1024, cfg.held) == 256 < 2048
    valid = jnp.arange(1024) < 1000
    got, chosen, load = jax.jit(
        lambda u, p, valid: unit_layers.moe_ffn(u, p, cfg, cfg.held, valid))(
        u, packed, valid)
    load = np.asarray(load)
    assert load.shape == (6,) and bool(load[5]) == overflows
    assert (load[4] > 256) == overflows and load[2] == 2000
    want, took = ref.experts(u, raw, bb, held=cfg.held)
    np.testing.assert_allclose(np.asarray(got)[:1000],
                               np.asarray(want)[:1000], rtol=0, atol=5e-5)
    assert np.array_equal(np.sort(np.asarray(chosen), -1),
                          np.sort(np.asarray(took), -1))
    # padding costs no product: only the shared expert reaches those rows
    shared = ref.swiglu(u, raw["shared_w1"], raw["shared_w3"],
                        raw["shared_w2"])
    np.testing.assert_allclose(np.asarray(got)[1000:],
                               np.asarray(shared)[1000:], rtol=0, atol=5e-5)
    graph = str(jax.make_jaxpr(lambda u, p: unit_layers.moe_ffn(
        u, p, cfg, cfg.held))(u, packed))
    assert "cond[" in graph and "bf16[256," not in graph
    assert "f32[256,64]" in graph      # the short path's rows


def test_the_bound_is_the_shapes_and_a_half_share_is_not_thin():
    real = pangu_moe.PanguConfig.from_dict(pangugen.backbone(REAL))
    # the cell's step: 2048 assignments, 64 expected on the 8 held, 256 taken
    assert unit_layers.held_rows(real, 256, real.held) == 256
    assert unit_layers.held_rows(real, 256 + 192, real.held) == 384
    # all of them where nothing is cut: a whole layer, a share of a half
    assert unit_layers.held_rows(real, 256, None) == 2048
    half = dataclasses.replace(real, num_experts=16, held=(0, 8))
    assert unit_layers.held_rows(half, 256, half.held) == 2048
    # a short prompt alone: no shorter than all its rows
    assert unit_layers.held_rows(real, 16, real.held) == 128


# -- the configuration -------------------------------------------------------

def test_the_configuration_is_read_as_the_module_says():
    cfg = pangu_moe.PanguConfig.from_dict(pangugen.backbone(REAL))
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.expert_layers) == (7, 1, [1, 2, 3, 4, 5, 6])
    assert (cfg.num_experts, cfg.held, cfg.num_experts_per_tok,
            cfg.vocab_size) == (256, (0, 8), 8, 19200)
    assert (cfg.latent_width, cfg.kv_lora_rank, cfg.q_lora_rank) == (
        576, 512, 1536)
    assert cfg.softmax_scale == 192 ** -0.5
    # a position: 640 lanes of bfloat16 a layer
    assert cfg.latent_cache_bytes(1) == 7 * 1280
    assert (cfg.norm_eps, cfg.routed_scaling_factor, cfg.expert_act,
            cfg.router_scoring, cfg.use_expert_bias,
            cfg.tie_word_embeddings) == (1e-5, 2.5, "swiglu", "sigmoid",
                                         False, False)
    with pytest.raises(ValueError, match="sandwich"):
        pangu_moe.PanguConfig.from_dict(dict(BB, sandwich_norm=False))
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        pangu_moe.PanguConfig.from_dict(dict(BB, first_k_dense_replace=9))
    with pytest.raises(ValueError, match="held"):
        pangu_moe.PanguConfig.from_dict(dict(BB, n_routed_experts=3))
    with pytest.raises(ValueError, match="vocab_parallel"):
        pangu_moe.PanguConfig.from_dict(dict(
            BB, vocab_parallel={"vocab_size": 4096, "held": [512, 512]}))
    whole = pangu_moe.PanguConfig.from_dict(
        {k: v for k, v in BB.items()
         if k not in ("expert_parallel", "vocab_parallel")})
    assert (whole.num_experts, whole.held, whole.vocab_size) == (
        2, (0, 2), 512)


# -- the voice and what its loop records ------------------------------------

def test_the_voice_runs_and_its_loop_says_what_the_cache_and_the_share_cost(
        tmp_path, monkeypatch):
    monkeypatch.undo()          # the voice as served: bfloat16 products
    monkeypatch.setenv("SONATA_AR_SLOTS", "3")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "256")
    # a short path short enough to overflow at this size: 2 rows
    monkeypatch.setattr(
        unit_layers, "held_rows", lambda cfg, tokens, held: min(
            tokens * cfg.num_experts_per_tok, 2))
    voice = from_config_path(pangugen.write_tensors(tmp_path, CONFIG))
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    tracer = tracing.default_tracer()
    tracer.clear()
    row_bytes = 4 * 128 * 2             # 4 layers, 40 values in 128 lanes
    resident = stats.resident["sonata_mla_cache_resident_bytes"]
    try:
        assert type(voice.backbone).__name__ == "PanguBackbone"
        described = voice.description
        assert (described.static, voice.attention, voice.expert_layers,
                voice.expert_matmul) == (
            {"ssm_layers": 0, "latent_layers": 4, "mla_form": "absorbed"},
            "einsum", [1, 2, 3], "ragged_dot")
        assert described.closed({"kv_positions": 10, "live_slot_steps": 2}) \
            == {"ssm_state_bytes": 0, "latent_cache_bytes": 10 * row_bytes}
        assert [row_sums(described, n) for n in (0, 10)] == [
            {"latent_places_fetched": places, "kv_places_fetched": 0}
            for places in (0, 256)]
        assert described.resident == {
            "sonata_mla_cache_resident_bytes": 3 * 256 * row_bytes}
        assert ("step_admit", 32) in voice.lattice_shapes("full")
        with tracer.trace_request("test", request_id="row-0"):
            audio = voice.speak_batch(
                list(voice.phonemize_text("one short row.")))
        ids = voice.config.phonemes_to_ids(
            list(voice.phonemize_text("one short row."))[0])
        assert len(audio[0].samples) == 16 * round(3.5 * len(ids))
        held = resident + 3 * 256 * row_bytes
        assert stats.resident["sonata_mla_cache_resident_bytes"] == held
        assert f"sonata_mla_cache_resident_bytes {held}\n" \
            in registry.render()
    finally:
        voice.close()
    assert stats.resident["sonata_mla_cache_resident_bytes"] == resident
    traces = {t.request_id: t for t in tracer.recent_traces()}
    (prefill,) = [s.attrs for s in traces["row-0"].spans_snapshot()
                  if s.attrs.get("kind") == "prefill"]
    assert (prefill["admit"], prefill["step_no"], prefill["mla_form"]) == (
        "step", 0, "expanded")
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups
    for g in groups:
        assert (g["latent_layers"], g["mla_form"], g["ssm_layers"]) == (
            4, "absorbed", 0)
        assert g["latent_cache_bytes"] == row_bytes * g["kv_positions"]
        # off a TPU the einsum moves every position of each live row's slot
        # (the row a launch carries is live from the next one on)
        assert g["latent_places_fetched"] == 256 * (
            g["live_slot_steps"] - g["admit_steps"]) >= g["kv_positions"]
        assert 0 <= g["held_overflow_steps"] <= g["steps"]
        assert all(0 <= h <= a for h, a in zip(g["held_assignments"],
                                               g["assignments"]))
    # with a short path of 2 rows some launch gave the held more
    overflowed = sum(g["held_overflow_steps"] for g in groups)
    assert 0 < overflowed
    text = registry.render()
    assert f"sonata_moe_held_overflow_steps_total {stats.held_overflow_steps}"\
        in text and stats.held_overflow_steps >= overflowed
    fetched = sum(g["latent_places_fetched"] for g in groups)
    assert f"sonata_mla_places_fetched_total {stats.mla_places_fetched}\n" \
        in text and stats.mla_places_fetched >= fetched > 0
