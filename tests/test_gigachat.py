"""The GigaChat-3.5 backbone (``sonata_tpu/models/gigachat.py``) against the
plain reference (``perfbench/reference/gigachat_ref.py``) at a tiny size on
the CPU, float32, seeded: every kind of layer, the chunked form of the
delta rule against the recurrence, prefill then steps through the slot's
states and latent rows against the reference's full pass, a slot reused,
the 4 shares of an expert layer against the uncut layer and the 8 slices of
the vocabulary against the whole head, the configuration, and the voice
with what its loop records."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import gigachatgen, parts
from sonata_tpu.models import from_config_path, gigachat, pangu_moe, \
    unit_layers
from sonata_tpu.models.config import SynthesisConfig
from sonata_tpu.models.unit_backbone import routes_of
from sonata_tpu.serving import tracing
from sonata_tpu.serving.metrics import MetricsRegistry
from tests.voices import row_sums

ROOT = Path(__file__).resolve().parent.parent
CONFIG = json.loads((ROOT / "tests/perfbench/data/gigachat-tiny.json"
                     ).read_text())
REAL = json.loads((ROOT / "perfbench/configs/gigachat/"
                   "gigachat3.5-432b-a28b.json").read_text())
BB = gigachatgen.backbone(CONFIG)
CFG = gigachat.GigaChatConfig.from_dict(BB)
UNITS = unit_layers.UnitIds(256, 511)
LAYERS = CFG.num_hidden_layers
PROMPTS = {"short": (5, 16), "whole_bucket": (16, 16), "two_chunks": (70, 96)}
ref = parts.load_file(ROOT / "perfbench/reference/gigachat_ref.py")
#: the programs as the voice jits them (traced under ``float32_products``)
PREFILL = jax.jit(functools.partial(gigachat.prefill, cfg=CFG, units=UNITS))
STEP = jax.jit(functools.partial(gigachat.step, cfg=CFG, units=UNITS))


def wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(autouse=True)
def float32_products(monkeypatch):
    """The program's products take bfloat16 inputs; here they take float32
    at ``highest``, so that it can be held to the reference to rounding."""
    for module in (unit_layers, pangu_moe, gigachat):
        monkeypatch.setattr(module, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def raw():
    return [wide(gigachatgen.draw_layer(CONFIG, i)) for i in range(LAYERS)]


@pytest.fixture(scope="module")
def params(raw):
    return {"embed": wide(gigachatgen.draw(CONFIG, "embed")),
            "head": wide(gigachatgen.draw(CONFIG, "head")),
            "norm_f": wide(gigachatgen.draw(CONFIG, "norm_f")),
            "layers": [gigachat.pack_layer(r, CFG) for r in raw]}


def prompt(n: int, bucket: int, seed: int = 0):
    ids = np.random.default_rng(seed + n).integers(0, 256, size=(n,))
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = ids
    return ids.tolist(), jnp.asarray(padded)


def hidden(t: int, seed: int):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (t, CFG.hidden_size)), jnp.float32)


def run_row(params, cache, slot: int, ids: list, padded, steps: int):
    """Prefill into ``slot`` and ``steps`` greedy steps of that slot alone:
    the logits of every launch, the tokens fed, and the cache."""
    cache, logits, _ = PREFILL(
        params, cache, padded, jnp.int32(len(ids)), jnp.int32(slot),
        jnp.float32(0.0), jax.random.PRNGKey(0))
    live = jnp.arange(cache["pos"].shape[0]) == slot
    got, tokens = [np.asarray(logits)], list(ids)
    for k in range(steps):
        tokens.append(int(cache["token"][slot]))
        cache, out, _ = STEP(params, cache, live, jnp.zeros(live.shape), k)
        got.append(np.asarray(out[slot]))
    return np.stack(got), tokens, cache


# -- layer by layer ----------------------------------------------------------

def test_a_linear_mixer_is_the_references_in_both_forms(raw):
    """Layer 0's gated DeltaNet over 70 positions: the chunked form (two
    chunks, a ragged tail) and the recurrence a position at a time through
    a slot's state, output and state against the reference's."""
    p = gigachat.pack_layer(raw[0], CFG)["mixer"]
    t = 70
    x = hidden(t, 3)
    want, (state, columns) = ref.delta_net(x, raw[0]["mixer"], BB)
    qkv, z, b, a = gigachat.delta_in(x, p, CFG)
    o, left, conv = gigachat.delta_seq(qkv, b, a, p, CFG, t)
    np.testing.assert_allclose(gigachat.delta_out(o, z, p, CFG), want,
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(left, state, rtol=0, atol=2e-5)
    np.testing.assert_allclose(conv, columns, rtol=0, atol=1e-6)
    slots = gigachat.new_cache(CFG, 1, 8)
    held, cols = slots["delta"][0], slots["conv"][0]
    step = jax.jit(lambda *args: gigachat.delta_step(*args[:3], p, CFG,
                                                     *args[3:]))
    for k in range(t):
        one, held, cols = step(qkv[k:k + 1], b[k:k + 1], a[k:k + 1], held,
                               cols)
        np.testing.assert_allclose(one[0], o[k], rtol=0, atol=2e-5)
    np.testing.assert_allclose(held[0], state, rtol=0, atol=2e-5)
    np.testing.assert_allclose(cols[0], columns, rtol=0, atol=1e-6)


@pytest.mark.parametrize("t,n", [(64, 64), (192, 192), (150, 137), (16, 5)],
                         ids=["one_chunk", "three_chunks", "ragged_tail",
                              "short_in_a_bucket"])
def test_the_chunked_form_is_the_recurrence(raw, t, n):
    """``delta_seq`` over a padded prompt against ``delta_step`` over its
    ``n`` real positions: every output and the state left.  Padding does
    not move the state."""
    p = gigachat.pack_layer(raw[2], CFG)["mixer"]
    qkv, _, b, a = gigachat.delta_in(hidden(t, t + n), p, CFG)
    o, left, conv = gigachat.delta_seq(qkv, b, a, p, CFG, n)
    step = jax.jit(lambda *args: gigachat.delta_step(*args[:3], p, CFG,
                                                     *args[3:]))
    slots = gigachat.new_cache(CFG, 1, 8)
    held, cols = slots["delta"][0], slots["conv"][0]
    for k in range(n):
        one, held, cols = step(qkv[k:k + 1], b[k:k + 1], a[k:k + 1], held,
                               cols)
        np.testing.assert_allclose(one[0], o[k], rtol=0, atol=3e-5)
    np.testing.assert_allclose(left, held[0], rtol=0, atol=3e-5)
    assert np.array_equal(conv, cols[0])


def test_the_full_layers_attention_is_the_references_in_both_forms(raw):
    """Layer 1's gated latent attention over 9 positions: the expanded
    form whole, the absorbed form a position at a time through a cache,
    YaRN's paces, the scaled softmax and the gate against the reference."""
    from sonata_tpu.ops import slot_attention

    p = gigachat.pack_layer(raw[1], CFG)["mixer"]
    t = 9
    x = hidden(t, 4)
    q_nope, q_rope, row = gigachat._latent_in(x, p, CFG, jnp.arange(t))
    whole = pangu_moe.mla_seq(q_nope, q_rope, row, p, CFG)
    np.testing.assert_allclose(
        gigachat._latent_out(x, whole, p),
        ref.attention(x, raw[1]["mixer"], BB), rtol=0, atol=2e-5)
    buf = jnp.zeros(slot_attention.stored_shape(1, 16, 1, CFG.latent_width))
    for k in range(t):
        buf = slot_attention.write_rows(buf, row[k:k + 1, None],
                                        jnp.asarray([[k]]))
        one = pangu_moe.mla_step(q_nope[k:k + 1], q_rope[k:k + 1], buf,
                                 jnp.asarray([k + 1]), p, CFG)
        np.testing.assert_allclose(one[0], whole[k], rtol=0, atol=2e-5)
    # the gate and the scale are there to be missed
    ungated = ref.attention(x, raw[1]["mixer"], BB, {"attn_gate": False})
    assert np.abs(np.asarray(ungated) - np.asarray(
        gigachat._latent_out(x, whole, p))).max() > 0.05
    assert CFG.softmax_scale == pytest.approx(
        24 ** -0.5 * (0.1 * np.log(8.0) + 1.0) ** 2)


def test_the_norms_and_the_clamped_feed_forwards_are_the_references(raw):
    """A zero-centred gated norm is ``rms_norm`` under its packed gain; the
    dense SwiGLU and a share of the expert layer under the clamp, on inputs
    wide enough that it binds."""
    x = 3.0 * hidden(13, 6)
    p = [gigachat.pack_layer(r, CFG) for r in raw[:2]]
    for name in gigachat.NORMS:
        np.testing.assert_allclose(
            unit_layers.rms_norm(x, p[0][name], CFG.norm_eps),
            ref.norm(x, raw[0][name], BB), rtol=0, atol=1e-5)
    dense = raw[0]["ffn"]
    assert float(jnp.abs(x @ dense["w1"]).max()) > CFG.swiglu_limit
    np.testing.assert_allclose(
        gigachat._ffn(x, p[0], 0, CFG, None, [], []),
        ref.swiglu(x, dense["w1"], dense["w3"], dense["w2"], BB), rtol=1e-5,
        atol=5e-4)
    assert np.abs(np.asarray(ref.swiglu(
        x, dense["w1"], dense["w3"], dense["w2"], BB, {"clamp": False}))
        - np.asarray(gigachat._ffn(x, p[0], 0, CFG, None, [], []))).max() > 1
    routes = []
    got = gigachat._ffn(x, p[1], 1, CFG, None, routes, [])
    want, chosen = ref.experts(x, raw[1]["ffn"], BB, held=CFG.held)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-4)
    assert np.array_equal(np.sort(routes[0], -1), np.sort(chosen, -1))


# -- whole rows ----------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_prefill_then_steps_give_the_references_full_pass(name, raw, params):
    """Logits of the prefill (chunked and expanded forms) and of 12 steps
    through the slot's states and latent rows (the recurrence, the absorbed
    form) against one whole pass of the reference over prompt + units; the
    experts chosen, the state every linear layer is left with and the
    cached rows too."""
    n, bucket = PROMPTS[name]
    ids, padded = prompt(n, bucket)
    got, tokens, cache = run_row(params, gigachat.new_cache(CFG, 3, 128), 1,
                                 ids, padded, 12)
    want, routes, states = ref.forward(
        jnp.asarray(tokens), BB, params["embed"], params["head"],
        params["norm_f"], lambda i: raw[i], held=CFG.held)
    np.testing.assert_allclose(got, np.asarray(want)[n - 1:], rtol=0,
                               atol=2e-4)
    served = routes_of(CFG, np.asarray(cache["routes"][1]))[:len(tokens)]
    assert served.dtype == np.uint8 and served.shape[1:] == (3, 2)
    assert np.array_equal(np.sort(served, -1), np.sort(routes, -1))
    assert len(states) == len(cache["delta"]) == 3
    for held, state in zip(cache["delta"], states):
        np.testing.assert_allclose(held[1], state, rtol=0, atol=2e-5)
    assert cache["latent"][0].shape[-1] == 128  # 40 values in whole lanes
    assert np.asarray(cache["latent"][0][1, :len(tokens)]).any()


def test_a_slot_a_row_has_left_gives_what_a_fresh_slot_gives(params):
    """A state is not masked by position: the prefill writes the slot's
    whole, and the second row of a slot does not see the first one's.  To
    the bit."""
    first, first_padded = prompt(19, 32)
    second, second_padded = prompt(11, 16, seed=7)
    _, _, used = run_row(params, gigachat.new_cache(CFG, 2, 64), 1, first,
                         first_padded, 9)
    assert np.asarray(used["delta"][-1][1]).any()
    again, tokens, _ = run_row(params, used, 1, second, second_padded, 9)
    fresh, fresh_tokens, _ = run_row(
        params, gigachat.new_cache(CFG, 2, 64), 1, second, second_padded, 9)
    assert tokens == fresh_tokens and np.array_equal(again, fresh)


# -- the shares ----------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer_the_shared_expert_once(raw):
    """``held = (0, 2)``, ``(2, 2)``, ``(4, 2)`` and ``(6, 2)`` of 8, what
    every chip computes alike (the shared expert) counted once, against the
    reference's whole layer; each share against the reference's share."""
    rng = np.random.default_rng(11)
    whole = dict(raw[1]["ffn"])
    for k in ("w1", "w3", "w2"):
        more = rng.uniform(-1, 1, (6,) + whole[k].shape[1:]) * float(
            jnp.abs(whole[k]).max())
        whole[k] = jnp.concatenate([whole[k], jnp.asarray(more, jnp.float32)])
    u = hidden(13, 5)
    want, chosen = ref.experts(u, whole, BB)
    shared = ref.swiglu(u, whole["shared_w1"], whole["shared_w3"],
                        whole["shared_w2"], BB)
    total = -3 * shared
    for first in (0, 2, 4, 6):
        share = dict(whole, **{k: whole[k][first:first + 2]
                               for k in ("w1", "w3", "w2")})
        packed = gigachat.pack_layer(dict(raw[1], ffn=share), CFG)["ffn"]
        got, took, _ = unit_layers.moe_ffn(u, packed, CFG, (first, 2))
        part, _ = ref.experts(u, share, BB, held=(first, 2))
        np.testing.assert_allclose(got, part, rtol=1e-5, atol=2e-4)
        assert np.array_equal(np.sort(took, -1), np.sort(chosen, -1))
        total = total + got
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=5e-4)


def test_the_vocabulary_slices_side_by_side_are_the_whole_head(params):
    """Eight chips hold 64 rows each of a head of 512: their logits laid
    side by side are the whole head's, and this chip's are the first."""
    h = hidden(5, 8)
    placed = dict(params, norm_f=gigachat.gain(params["norm_f"], CFG))
    whole = unit_layers._head(h, placed, CFG)
    slices = [unit_layers._head(
        h, dict(placed, head=params["head"][k:k + 64]), CFG)
        for k in range(0, 512, 64)]
    np.testing.assert_allclose(jnp.concatenate(slices, -1), whole, rtol=0,
                               atol=1e-5)
    want = ref.head(h, params["head"][:64], params["norm_f"], BB)
    np.testing.assert_allclose(slices[0], want, rtol=0, atol=1e-5)


# -- the configuration -------------------------------------------------------

def test_the_configuration_is_read_as_the_module_says():
    cfg = gigachat.GigaChatConfig.from_dict(gigachatgen.backbone(REAL))
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.full_attention_layers, cfg.linear_layers,
            cfg.expert_layers) == (5, 1, (1,), [0, 2, 3, 4], [1, 2, 3, 4])
    assert (cfg.num_experts, cfg.held, cfg.num_experts_per_tok,
            cfg.vocab_size) == (256, (0, 8), 8, 16032)
    assert (cfg.latent_width, cfg.conv_dim, cfg.key_width,
            cfg.value_width) == (576, 16384, 4096, 8192)
    # a slot: four layers of 64 matrices of 128 x 128 and three columns of
    # 16 384, float32; a position: 640 lanes of bfloat16 in the one full
    # layer
    assert cfg.delta_state_bytes == 4 * 4 * (64 * 128 * 128 + 3 * 16384)
    assert cfg.latent_cache_bytes(1) == 1280
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.2079 ** 2,
                                              rel=1e-4)
    assert (cfg.rotary.dims, cfg.rotary.factor,
            len(cfg.rotary.inv_freq)) == (64, 1.0, 32)
    assert (cfg.norm_eps, cfg.routed_scaling_factor, cfg.expert_act,
            cfg.swiglu_limit, cfg.router_scoring, cfg.use_expert_bias,
            cfg.tie_word_embeddings) == (1e-6, 2.5, "swiglu_clamped", 10.0,
                                         "sigmoid", True, False)
    with pytest.raises(ValueError, match="zero-centred"):
        gigachat.GigaChatConfig.from_dict(dict(BB, norm_type="RMSNorm"))
    with pytest.raises(ValueError, match="full_attention_layers"):
        gigachat.GigaChatConfig.from_dict(dict(BB,
                                               full_attention_layers=[7]))
    with pytest.raises(ValueError, match="expert_parallel"):
        gigachat.GigaChatConfig.from_dict(dict(BB, n_routed_experts=3))
    with pytest.raises(ValueError, match="id 0"):
        gigachat.GigaChatConfig.from_dict(dict(
            BB, vocab_parallel={"vocab_size": 4096, "held": [512, 512]}))
    whole = gigachat.GigaChatConfig.from_dict(
        {k: v for k, v in BB.items()
         if k not in ("expert_parallel", "vocab_parallel")})
    assert (whole.num_experts, whole.held, whole.vocab_size) == (
        2, (0, 2), 512)


# -- the voice and what its loop records ------------------------------------

def test_the_voice_runs_and_its_loop_says_what_its_state_costs(
        tmp_path, monkeypatch):
    monkeypatch.undo()          # the voice as served: bfloat16 products
    monkeypatch.setenv("SONATA_AR_SLOTS", "3")
    monkeypatch.setenv("SONATA_AR_POSITIONS", "256")
    voice = from_config_path(gigachatgen.write_tensors(tmp_path, CONFIG))
    voice.set_fallback_synthesis_config(SynthesisConfig(noise_scale=0.0))
    registry = MetricsRegistry()
    stats = tracing.step_stats()
    stats.bind_metrics(registry)
    tracer = tracing.default_tracer()
    tracer.clear()
    state = 4 * 3 * (4 * 16 * 16 + 3 * 128)     # 3 linear layers, float32
    row_bytes = 128 * 2                 # 1 full layer, 40 values in 128 lanes
    before = dict(stats.resident)
    try:
        assert type(voice.backbone).__name__ == "GigaChatBackbone"
        described = voice.description
        assert (described.static, voice.attention, voice.expert_layers,
                voice.expert_matmul) == (
            {"ssm_layers": 0, "latent_layers": 1, "delta_layers": 3,
             "mla_form": "absorbed"}, "einsum", [1, 2, 3], "ragged_dot")
        assert described.closed({"kv_positions": 10, "live_slot_steps": 2}) \
            == {"ssm_state_bytes": 0, "latent_cache_bytes": 10 * row_bytes,
                "delta_state_bytes": 2 * state * 2}
        assert [row_sums(described, n) for n in (0, 10)] == [
            {"latent_places_fetched": places, "kv_places_fetched": 0}
            for places in (0, 256)]
        assert described.resident == {
            "sonata_delta_state_resident_bytes": 3 * state,
            "sonata_mla_cache_resident_bytes": 3 * 256 * row_bytes}
        assert described.prefill(96) == {"delta_chunks": 6,
                                         "mla_form": "expanded"}
        assert ("step_admit", 32) in voice.lattice_shapes("full")
        with tracer.trace_request("test", request_id="row-0"):
            audio = voice.speak_batch(
                list(voice.phonemize_text("one short row.")))
        ids = voice.config.phonemes_to_ids(
            list(voice.phonemize_text("one short row."))[0])
        assert len(audio[0].samples) == 16 * round(3.5 * len(ids))
        held = before["sonata_delta_state_resident_bytes"] + 3 * state
        assert stats.resident["sonata_delta_state_resident_bytes"] == held
        assert f"sonata_delta_state_resident_bytes {held}\n" \
            in registry.render()
        # a device with room for no second cache beside the idle loop's
        # (the server speaks once before it warms the lattice): the loop is
        # let go, one warm-up dispatch holds a cache, the next row starts
        # another loop
        need = sum(a.size * a.dtype.itemsize for a in
                   jax.tree_util.tree_leaves(jax.eval_shape(voice.new_cache)))
        voice._free_bytes = lambda: (
            3 * need if voice._loop is None else 3 * need // 2)
        assert voice._loop is not None and voice._warm_caches is None
        assert voice._warm_cache_slots()._value == 2 and voice._loop is None
        assert stats.resident == before
        again = voice.speak_batch(list(voice.phonemize_text("one short row.")))
        assert np.array_equal(again[0].samples, audio[0].samples)
        # with room for one beside it the loop stands
        voice._warm_caches, voice._free_bytes = None, lambda: 2 * need
        assert voice._warm_cache_slots()._value == 1
        assert voice._loop is not None
    finally:
        voice.close()
    assert stats.resident == before
    traces = {t.request_id: t for t in tracer.recent_traces()}
    (prefill,) = [s.attrs for s in traces["row-0"].spans_snapshot()
                  if s.attrs.get("kind") == "prefill"]
    assert (prefill["admit"], prefill["delta_chunks"],
            prefill["mla_form"]) == ("step", 3 * -(-prefill["text_bucket"]
                                                   // 64), "expanded")
    groups = [s.attrs for rid, t in traces.items()
              if rid.startswith("ar-steps-") for s in t.spans_snapshot()
              if s.name == "dispatch"]
    assert groups
    for g in groups:
        assert (g["delta_layers"], g["latent_layers"], g["ssm_layers"]) == (
            3, 1, 0)
        assert g["delta_state_bytes"] == 2 * state * g["live_slot_steps"]
        assert g["latent_cache_bytes"] == row_bytes * g["kv_positions"]
