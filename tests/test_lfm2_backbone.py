"""The LFM2-MoE backbone (``sonata_tpu/models/lfm2.py``) against the plain
reference (``perfbench/reference/lfm2_ref.py``) at a tiny size on the CPU,
float32, seeded: every layer kind, the whole backbone through prefill and
decode steps in slots, the expert layer's shares, the routing rule and the
sampling rule."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import lfm2gen, parts
from sonata_tpu.models import lfm2, unit_layers
from sonata_tpu.models.unit_backbone import routes_of

ROOT = Path(__file__).resolve().parent.parent
CONFIG = json.loads((ROOT / "tests/perfbench/data/lfm2-tiny.json").read_text())
BB = lfm2gen.backbone(CONFIG)
CFG = lfm2.Lfm2Config.from_dict(BB)
UNITS = unit_layers.UnitIds(256, 511)
ref = parts.load_file(ROOT / "perfbench/reference/lfm2_ref.py")


def wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(autouse=True)
def float32_products(monkeypatch):
    """The program's products take bfloat16 inputs; here they take float32
    at ``highest``, so that it can be held to the reference to rounding."""
    monkeypatch.setattr(unit_layers, "BF16", jnp.float32)
    monkeypatch.setattr(lfm2, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def raw():
    return [wide(lfm2gen.draw_layer(CONFIG, i))
            for i in range(len(CFG.layer_types))]


@pytest.fixture(scope="module")
def params(raw):
    return {"embed": wide(lfm2gen.draw(CONFIG, "embed")),
            "norm_f": wide(lfm2gen.draw(CONFIG, "norm_f")),
            "layers": [lfm2.pack_layer(r) for r in raw]}


@pytest.fixture(scope="module")
def u():
    return jnp.asarray(np.random.default_rng(5).standard_normal(
        (13, CFG.hidden_size)), jnp.float32)


def close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("kind", ["conv_op", "attn_op", "dense_ffn",
                                  "moe_ffn"])
def test_each_layer_kind_matches_the_reference(kind, raw, params, u):
    if kind == "conv_op":
        got, state = lfm2.conv_op_seq(u, params["layers"][0]["op"], 13)
        close(got, ref.conv_op(u, raw[0]["op"], BB))
        b, _, x = jnp.split(u @ raw[0]["op"]["in_proj"], 3, axis=-1)
        close(state, (b * x)[-3:])
    elif kind == "attn_op":
        got, k, v = unit_layers.attn_op_seq(u, params["layers"][2]["op"], CFG)
        close(got, ref.attn_op(u, raw[2]["op"], BB))
        assert k.shape == v.shape == (13, 2, 16)
    elif kind == "dense_ffn":
        close(unit_layers.dense_ffn(u, params["layers"][1]["ffn"]),
              ref.dense_ffn(u, raw[1]["ffn"]))
    else:
        got, chosen, load = unit_layers.moe_ffn(
            u, params["layers"][3]["ffn"], CFG)
        want, want_chosen = ref.moe_ffn(u, raw[3]["ffn"], BB)
        close(got, want)
        assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
        picks = np.asarray(chosen).reshape(-1)
        assert load.tolist() == [len(set(picks)),
                                 np.bincount(picks).max(), 26]


def test_the_whole_backbone_matches_the_reference_at_every_position(
        raw, params):
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, 512, (21,)),
                         jnp.int32)
    want, want_routes = ref.forward(tokens, BB, params["embed"],
                                    params["norm_f"], lambda i: raw[i])
    h = params["embed"][tokens]
    routes = []
    for i, kind in enumerate(CFG.layer_types):
        p = params["layers"][i]
        un = unit_layers.rms_norm(h, p["op_norm"], CFG.norm_eps)
        h = h + (lfm2.conv_op_seq(un, p["op"], 21)[0] if kind == "conv"
                 else unit_layers.attn_op_seq(un, p["op"], CFG)[0])
        h = lfm2._ffn_half(h, p, i, CFG, None, None, routes, [])
    assert np.array_equal(np.stack(routes, 1), np.asarray(want_routes))
    close(unit_layers._head(h, params, CFG), want, 5e-5)
    assert want_routes.shape == (21, 4, 2)


def test_prefill_and_steps_through_slots_match_one_full_forward_pass(
        raw, params):
    """Rows of unequal prompt and length join and leave mid-run, and a
    slot is used again: logits at every position of every row against the
    reference's one full forward pass over prompt + chosen units."""
    prefill = jax.jit(lambda p, c, ids, n, slot: lfm2.prefill(
        p, c, ids, n, slot, jnp.float32(0.0), jax.random.PRNGKey(0),
        cfg=CFG, units=UNITS))
    step = jax.jit(lambda p, c, live, k: lfm2.step(
        p, c, live, jnp.zeros((3,), jnp.float32), k, cfg=CFG, units=UNITS))
    rng = np.random.default_rng(11)
    # (joins before step, slot, prompt ids, units)
    plan = [(0, 0, 9, 6), (0, 2, 14, 4), (2, 1, 5, 7), (5, 2, 11, 5)]
    cache = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        lfm2.new_cache(CFG, 3, 32))
    rows, done = {}, []
    for k in range(12):
        for join, slot, n, units in plan:
            if join == k:
                assert slot not in rows
                ids = rng.integers(0, 256, (n,)).astype(np.int32)
                padded = np.zeros((16,), np.int32)
                padded[:n] = ids
                cache, logits, _ = prefill(params, cache, padded, n, slot)
                rows[slot] = {"ids": ids, "units": units, "got": 1,
                              "logits": [np.asarray(logits)]}
        live = np.zeros((3,), bool)
        live[list(rows)] = True
        if not live.any():
            break
        cache, logits, load = step(params, cache, live, k)
        assert int(load[0][2]) == 2 * int(live.sum())
        for slot in list(rows):
            row = rows[slot]
            row["logits"].append(np.asarray(logits[slot]))
            row["got"] += 1
            if row["got"] == row["units"]:
                row["chosen"] = np.asarray(cache["units"][slot,
                                                          :row["units"]])
                row["routes"] = routes_of(
                    CFG, np.asarray(cache["routes"][slot]))
                done.append(rows.pop(slot))
    assert len(done) == 4 and not rows
    assert not any((row["chosen"] == UNITS.stop_id).any() for row in done)
    # the reference over all four rows at once, each padded to 32 tokens
    # (every operator is causal: what follows a row's end does not reach it)
    tokens = np.zeros((4, 32), np.int32)
    for k, row in enumerate(done):
        row["tokens"] = np.concatenate([row["ids"], row["chosen"][:-1]])
        tokens[k, :len(row["tokens"])] = row["tokens"]
    want, routes = jax.vmap(lambda t: ref.forward(
        t, BB, params["embed"], params["norm_f"], lambda i: raw[i]))(
        jnp.asarray(tokens))
    for k, row in enumerate(done):
        n, t = len(row["ids"]), len(row["tokens"])
        close(np.stack(row["logits"]), want[k, n - 1:t], 5e-5)
        assert np.array_equal(row["routes"][:t], np.asarray(routes[k, :t]))
        # greedy over the unit ids, the stop unit left out
        allowed = np.asarray(want[k, n - 1:t])[:, 256:511]
        assert np.array_equal(row["chosen"], 256 + allowed.argmax(-1))


def test_the_reference_on_forced_routes(raw, u):
    """``forced``: the experts handed in are computed, weighted by the
    layer's own scores of them; its own choice is still what it says."""
    p = raw[3]["ffn"]
    own, chosen = ref.moe_ffn(u, p, BB)
    same, said = ref.moe_ffn(u, p, BB, forced=chosen)
    close(same, own, 1e-6)
    assert np.array_equal(np.asarray(said), np.asarray(chosen))
    other = (chosen + 1) % CFG.num_experts
    got, said = ref.moe_ffn(u, p, BB, forced=other)
    assert np.array_equal(np.asarray(said), np.asarray(chosen))
    scores = np.asarray(jax.nn.sigmoid(u @ p["router"]))
    want = np.zeros_like(np.asarray(own))
    for t, experts in enumerate(np.asarray(other)):
        weights = scores[t, experts] / (scores[t, experts].sum() + 1e-6)
        for e, w in zip(experts, weights):
            want[t] += w * np.asarray(ref.swiglu(
                u[t], p["w1"][e], p["w3"][e], p["w2"][e]))
    close(got, want)
    assert not np.allclose(np.asarray(got), np.asarray(own), atol=1e-3)


def held_layer(raw_ffn, first, count):
    """The share of an expert layer that holds ``count`` experts from
    ``first``: every expert's router column, its own experts' weights."""
    cut = dict(raw_ffn)
    for name in ("w1", "w3", "w2"):
        cut[name] = raw_ffn[name][first:first + count]
    return cut


@pytest.mark.parametrize("shares", [[(0, 2), (2, 2), (4, 2), (6, 2)],
                                    [(0, 8)], [(0, 3), (3, 5)]])
def test_the_shares_add_up_to_the_uncut_reference_layer(shares, raw, u):
    want, want_chosen = ref.moe_ffn(u, raw[4]["ffn"], BB)
    total = 0.0
    for first, count in shares:
        packed = lfm2.pack_layer(dict(raw[4], ffn=held_layer(
            raw[4]["ffn"], first, count)))
        part, chosen, load = unit_layers.moe_ffn(u, packed["ffn"], CFG,
                                          held=(first, count))
        # every share routes over all the experts and says so
        assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
        assert int(load[2]) == 26
        close(part, ref.moe_ffn(u, held_layer(raw[4]["ffn"], first, count),
                                BB, held=(first, count))[0])
        total = total + part
    close(total, want)
    with pytest.raises(ValueError, match="held"):
        unit_layers.moe_ffn(u, lfm2.pack_layer(raw[4])["ffn"], CFG,
                            held=(0, 2))


def test_the_bias_changes_the_selection_but_not_the_weights(raw, u):
    ffn = lfm2.pack_layer(raw[3])["ffn"]
    plain = dict(ffn, expert_bias=jnp.zeros_like(ffn["expert_bias"]))
    pushed = dict(ffn, expert_bias=jnp.zeros_like(
        ffn["expert_bias"]).at[5].set(10.0))
    chosen_0, weights_0 = unit_layers.route(u, plain, CFG)
    chosen_1, weights_1 = unit_layers.route(u, pushed, CFG)
    assert not np.array_equal(np.asarray(chosen_0), np.asarray(chosen_1))
    assert np.all(np.asarray(chosen_1)[:, 0] == 5)
    scores = jax.nn.sigmoid(u @ ffn["router"])
    for chosen, weights in ((chosen_0, weights_0), (chosen_1, weights_1)):
        s = jnp.take_along_axis(scores, chosen, -1)
        close(weights, s / (s.sum(-1, keepdims=True) + 1e-6))
    close(weights_1.sum(-1), 1.0, 1e-4)


#: a row's temperature in :func:`unit_layers.choose`'s tests: 0 is greedy
TEMPERATURES = {"greedy": [0.0] * 6,
                "drawn": [0.667, 0.667, 1.0, 2.0, 5.0, 0.1],
                "mixed": [0.0, 0.667, 0.0, 5.0, 0.0, 0.2]}


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
@pytest.mark.parametrize("rows", sorted(TEMPERATURES))
def test_choose_gives_an_id_and_its_log_probability(rows, impl):
    """``choose``: at temperature 0 the arg-max over the allowed ids bit
    for bit, else what ``jax.random.categorical`` draws from the scaled
    logits with the key; the log-probability is ``log_softmax`` of the
    allowed logits over the temperature at that id; ``sample`` is its ids;
    the stop unit never comes out."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(3.0 * rng.standard_normal((6, 512)), jnp.float32)
    logits = logits.at[:, 511].set(50.0).at[:, 7].set(60.0)
    logits = logits.at[0, 300].set(logits[0, 400])      # equals: the first
    t = jnp.asarray(TEMPERATURES[rows], jnp.float32)
    key = jax.random.key(11, impl=impl)
    ids, log_p = jax.jit(lambda x, t, k: unit_layers.choose(x, t, k, UNITS))(
        logits, t, key)
    assert ids.dtype == jnp.int32 and log_p.dtype == jnp.float32
    masked = jnp.where(unit_layers.allowed_ids(512, UNITS), logits, -jnp.inf)
    scaled = masked / jnp.where(t > 0, t, 1.0)[:, None]
    want = jnp.where(t > 0, jax.random.categorical(key, scaled, axis=-1),
                     jnp.argmax(masked, -1))
    assert np.array_equal(np.asarray(ids), np.asarray(want))
    assert 256 <= int(ids.min()) and int(ids.max()) < 511
    close(log_p, jnp.take_along_axis(jax.nn.log_softmax(scaled, -1),
                                     ids[:, None], -1)[:, 0], 1e-5)
    assert np.array_equal(
        np.asarray(unit_layers.sample(logits, t, key, UNITS)),
        np.asarray(ids))


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
@pytest.mark.parametrize("temperature", [0.667, 2.0])
def test_the_draws_follow_the_softmax_over_the_allowed_ids(temperature,
                                                           impl):
    """8192 keys, 30 allowed ids of 40: the draws' frequencies against
    ``softmax(logits / t)`` over the allowed ids by a chi-square bound (29
    degrees of freedom: 66.2 is passed once in ten thousand); the ids
    before the first unit, the mask and the stop id are never drawn, high
    as their logits are."""
    units = unit_layers.UnitIds(8, 39, 20)
    logits = jnp.asarray(
        0.7 * np.random.default_rng(7).standard_normal((1, 40)),
        jnp.float32).at[0, jnp.asarray([3, 20, 39])].set(9.0)
    keys = jax.random.split(jax.random.key(46, impl=impl), 8192)
    ids = np.asarray(jax.jit(jax.vmap(lambda k: unit_layers.choose(
        logits, jnp.full((1,), temperature), k, units)[0][0]))(keys))
    allowed = np.asarray(unit_layers.allowed_ids(40, units))
    assert allowed.sum() == 30
    counts = np.bincount(ids, minlength=40)
    assert counts[~allowed].sum() == 0
    p = np.exp(np.asarray(logits[0], np.float64) / temperature) * allowed
    expected = len(keys) * p / p.sum()
    assert expected[allowed].min() > 5
    chi2 = ((counts - expected)[allowed] ** 2 / expected[allowed]).sum()
    assert chi2 < 66.2


def test_sampling_is_greedy_at_zero_and_never_gives_the_stop_unit():
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((6, 512)), jnp.float32)
    logits = logits.at[:, 511].set(50.0).at[:, 7].set(60.0)
    key = jax.random.PRNGKey(1)
    greedy = unit_layers.sample(logits, jnp.zeros((6,)), key, UNITS)
    assert np.array_equal(np.asarray(greedy),
                          256 + np.asarray(logits)[:, 256:511].argmax(-1))
    mixed = jnp.asarray([0.0, 0.0, 0.0, 5.0, 5.0, 5.0])
    drawn = [np.asarray(unit_layers.sample(
        logits, mixed, jax.random.PRNGKey(k), UNITS)) for k in range(8)]
    assert all(np.array_equal(d[:3], np.asarray(greedy)[:3]) for d in drawn)
    assert len({tuple(d[3:]) for d in drawn}) > 1
    assert all(256 <= d.min() and d.max() < 511 for d in drawn)
