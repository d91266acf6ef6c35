"""The SDAR-MoE backbone (``sonata_tpu/models/sdar.py``) against the plain
reference (``perfbench/reference/sdar_ref.py``) at a tiny size on the CPU,
float32, seeded: the mask, the router, the expert layer's shares, prefill
and passes through the cache against the whole forward pass at every pass
of every row, whole rows against the published loop, and what the new
configuration fields leave of the ``lfm2`` programs."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import lfm2gen, parts, sdargen
from sonata_tpu.models import lfm2, sdar, unit_layers
from sonata_tpu.models.unit_backbone import routes_of
from tools import profile_sampler

ROOT = Path(__file__).resolve().parent.parent
CONFIG = json.loads((ROOT / "tests/perfbench/data/sdar-tiny.json").read_text())
BB = sdargen.backbone(CONFIG)
CFG = sdar.SdarConfig.from_dict(BB)
UNITS = unit_layers.UnitIds(256, 511, 510)
B = 4
SPAN = 64
ref = parts.load_file(ROOT / "perfbench/reference/sdar_ref.py")


def wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(autouse=True)
def float32_products(monkeypatch):
    """The program's products take bfloat16 inputs; here they take float32
    at ``highest``, so that it can be held to the reference to rounding."""
    monkeypatch.setattr(unit_layers, "BF16", jnp.float32)
    monkeypatch.setattr(sdar, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def raw():
    return [wide(sdargen.draw_layer(CONFIG, i))
            for i in range(CFG.num_hidden_layers)]


@pytest.fixture(scope="module")
def params(raw):
    return {"embed": wide(sdargen.draw(CONFIG, "embed")),
            "head": wide(sdargen.draw(CONFIG, "head")),
            "norm_f": wide(sdargen.draw(CONFIG, "norm_f")),
            "layers": [sdar.pack_layer(r) for r in raw]}


@pytest.fixture(scope="module")
def u():
    return jnp.asarray(np.random.default_rng(5).standard_normal(
        (13, CFG.hidden_size)), jnp.float32)


@pytest.fixture(scope="module")
def whole(raw, params):
    """The reference's whole forward pass over a sequence padded to
    ``SPAN`` (what follows a block does not reach it)."""
    fn = jax.jit(lambda t: ref.forward(
        t, BB, B, params["embed"], params["head"], params["norm_f"],
        lambda i: raw[i]))

    def logits_of(tokens):
        padded = np.zeros((SPAN,), np.int32)
        padded[:len(tokens)] = tokens
        logits, routes = fn(jnp.asarray(padded))
        return np.asarray(logits)[:len(tokens)], np.asarray(
            routes)[:len(tokens)]

    return logits_of


def close(a, b, tol=5e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


def float_cache(slots):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        sdar.new_cache(CFG, slots, SPAN))


def programs(steps: int, slots: int):
    schedule = sdar.Schedule(B, steps, UNITS.mask_id)
    prefill = jax.jit(lambda p, c, ids, n, slot: sdar.prefill(
        p, c, ids, n, slot, cfg=CFG, schedule=schedule))
    block_pass = jax.jit(lambda p, c, live, k: sdar.block_pass(
        p, c, live, jnp.zeros((slots,), jnp.float32), k, cfg=CFG,
        schedule=schedule, units=UNITS))
    return schedule, prefill, block_pass


@pytest.mark.parametrize("block", [1, 3, 4])
def test_the_mask_against_a_brute_force_loop(block):
    t = 11
    want = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            want[i, j] = j // block <= i // block
    assert np.array_equal(
        np.asarray(unit_layers.block_mask(jnp.arange(t), block)), want)
    assert np.array_equal(np.asarray(ref.block_mask(t, block)), want)
    if block == 1:
        assert np.array_equal(want, np.tril(np.ones((t, t), bool)))


def test_attention_over_a_prompt_is_whole_inside_a_block(raw, params, u):
    got, k, v = unit_layers.attn_op_seq(u, params["layers"][0]["attn"], CFG, B)
    close(got, ref.attn(u, raw[0]["attn"], BB, ref.block_mask(13, B),
                        jnp.arange(13)))
    assert k.shape == v.shape == (13, 2, 32)
    causal, _, _ = unit_layers.attn_op_seq(u, params["layers"][0]["attn"], CFG)
    close(causal, ref.attn(u, raw[0]["attn"], BB, ref.block_mask(13, 1),
                           jnp.arange(13)))
    assert not np.allclose(np.asarray(got), np.asarray(causal), atol=1e-3)


def test_the_softmax_router_against_the_reference(raw, params, u):
    ffn = params["layers"][1]["moe"]
    chosen, weights = unit_layers.route(u, ffn, CFG)
    want_chosen, want_weights, scores = ref.route(u, raw[1]["moe"], BB)
    assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
    close(weights, want_weights, 1e-6)
    close(weights.sum(-1), 1.0, 1e-6)
    close(scores.sum(-1), 1.0, 1e-5)
    # the weights are the chosen experts' share of a softmax over all
    s = jax.nn.softmax(u @ raw[1]["moe"]["router"], -1)
    top = np.sort(np.asarray(s), -1)[:, -2:]
    close(np.sort(np.asarray(weights), -1), top / top.sum(-1, keepdims=True),
          1e-6)
    got, said, load = unit_layers.moe_ffn(u, ffn, CFG)
    want, _ = ref.moe(u, raw[1]["moe"], BB)
    close(got, want)
    assert np.array_equal(np.asarray(said), np.asarray(want_chosen))
    assert int(load[2]) == 26
    # forced routes: the experts handed in, at the layer's own scores
    other = (want_chosen + 1) % CFG.num_experts
    forced, told = ref.moe(u, raw[1]["moe"], BB, forced=other)
    assert np.array_equal(np.asarray(told), np.asarray(want_chosen))
    assert not np.allclose(np.asarray(forced), np.asarray(want), atol=1e-3)
    close(ref.moe(u, raw[1]["moe"], BB, forced=want_chosen)[0], want, 1e-6)


def held_layer(raw_moe, first, count):
    cut = dict(raw_moe)
    for name in ("w1", "w3", "w2"):
        cut[name] = raw_moe[name][first:first + count]
    return cut


@pytest.mark.parametrize("shares", [[(0, 2), (2, 2), (4, 2), (6, 2)],
                                    [(0, 8)], [(0, 3), (3, 5)]])
def test_the_shares_add_up_to_the_uncut_reference_layer(shares, raw, u):
    want, want_chosen = ref.moe(u, raw[2]["moe"], BB)
    total = 0.0
    for first, count in shares:
        packed = sdar.pack_layer(dict(raw[2], moe=held_layer(
            raw[2]["moe"], first, count)))
        part, chosen, load = unit_layers.moe_ffn(u, packed["moe"], CFG,
                                          held=(first, count))
        # every share routes over all the experts and says so
        assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
        assert int(load[2]) == 26
        close(part, ref.moe(u, held_layer(raw[2]["moe"], first, count), BB,
                            held=(first, count))[0])
        total = total + part
    close(total, want)


def test_prefill_and_passes_through_slots_match_the_whole_forward_pass(
        params, whole):
    """Rows of unequal prompt and length join and leave mid-run, a slot is
    used again, and rows denoise and commit in one launch: at every pass of
    every row, the logits and the experts chosen against the reference's
    whole pass over the row's committed tokens + the block as it went in."""
    schedule, prefill, block_pass = programs(2, 3)
    rng = np.random.default_rng(11)
    # (joins before launch, slot, prompt ids, units)
    plan = [(0, 0, 9, 6), (0, 2, 12, 5), (1, 1, 7, 9), (8, 2, 10, 3)]
    cache = float_cache(3)
    rows, done, mixed = {}, 0, 0
    for k in range(20):
        for join, slot, n, budget in plan:
            if join == k:
                assert slot not in rows
                ids = rng.integers(0, 256, (n,)).astype(np.int32)
                padded = np.zeros((16,), np.int32)
                padded[:n] = ids
                cache, load = prefill(params, cache, padded, n, slot)
                # only the whole blocks' tokens go through the experts
                assert int(load[0][2]) == 2 * (n // B * B)
                blocks = -(-(n % B + budget) // B)
                rows[slot] = {"ids": ids, "budget": budget, "passes": 0,
                              "left": blocks * schedule.passes}
        live = np.zeros((3,), bool)
        live[list(rows)] = True
        if not live.any():
            break
        before = {s: (int(cache["start"][s]), int(cache["pass"][s]),
                      np.asarray(cache["tokens"][s])) for s in rows}
        cache, (x, logits, chose), load = block_pass(params, cache, live, k)
        assert int(load[0][2]) == 2 * B * int(live.sum())
        phases = {before[s][1] == schedule.denoising_steps for s in rows}
        mixed += len(phases) == 2
        for slot in list(rows):
            row = rows[slot]
            start, pass_no, tokens = before[slot]
            assert pass_no == row["passes"] % schedule.passes
            assert start == len(row["ids"]) // B * B + row[
                "passes"] // schedule.passes * B
            seq = np.concatenate([tokens[:start], np.asarray(x[slot])])
            assert np.array_equal(seq, tokens[:start + B])
            want, routes = whole(seq)
            close(logits[slot * B:slot * B + B], want[start:])
            assert np.array_equal(np.asarray(chose[slot]), routes[start:])
            masked = seq[start:] == UNITS.mask_id
            after = np.asarray(cache["tokens"][slot, start:start + B])
            if pass_no < schedule.denoising_steps:
                # the surest two of the masked positions are unmasked, each
                # with the reference's best unit; nothing else moves
                assert int(cache["pass"][slot]) == pass_no + 1
                allowed = want[start:, 256:510]
                best = 256 + allowed.argmax(-1)
                log_p = allowed.max(-1) - np.log(np.exp(allowed).sum(-1))
                surest = [j for j in np.argsort(-log_p, kind="stable")
                          if masked[j]][:2]
                expect = seq[start:].copy()
                expect[surest] = best[surest]
                assert np.array_equal(after, expect)
                assert np.asarray(cache["unmasked_at"][
                    slot, start:start + B])[surest].tolist() == [
                    pass_no] * len(surest)
            else:
                assert not masked.any() and np.array_equal(after, seq[start:])
                assert int(cache["start"][slot]) == start + B
                assert int(cache["pass"][slot]) == 0
                # the commit pass's experts are the ones the slot keeps
                assert np.array_equal(routes_of(CFG, np.asarray(
                    cache["routes"][slot]))[start:start + B], routes[start:])
            row["passes"] += 1
            if row["passes"] == row["left"]:
                n = len(row["ids"])
                units = np.asarray(cache["tokens"][slot, n:n + row["budget"]])
                assert ((units >= 256) & (units < 510)).all()
                done += 1
                del rows[slot]
    assert done == 4 and not rows and mixed > 3


@pytest.mark.parametrize("steps,n", [(1, 8), (1, 11), (2, 8), (2, 11),
                                     (4, 8), (4, 11)])
def test_a_whole_row_gives_the_published_loops_units(steps, n, params,
                                                     whole):
    """``low_confidence_static``, greedy, a prompt with ``n mod B`` of 0
    and of 3: prefill and passes through the cache choose what the
    reference loop (no cache: the whole sequence at every pass) chooses."""
    schedule, prefill, block_pass = programs(steps, 2)
    budget = 10
    ids = np.random.default_rng(n * 10 + steps).integers(
        0, 256, (n,)).astype(np.int32)
    want, passes = ref.generate(
        ids.tolist(), budget, lambda t: whole(t)[0], block=B, steps=steps,
        mask_id=UNITS.mask_id, first_id=256, stop_id=511)
    blocks = -(-(n % B + budget) // B)
    assert len(passes) == blocks * (steps + 1) and len(want) == budget
    padded = np.zeros((16,), np.int32)
    padded[:n] = ids
    cache, _ = prefill(params, float_cache(2), padded, n, 1)
    live = np.array([False, True])
    for k, (seq, logits) in enumerate(passes):
        cache, (x, got, _), _ = block_pass(params, cache, live, k)
        assert np.array_equal(np.asarray(x[1]), seq[-B:])
        close(got[B:2 * B], logits)
    assert np.array_equal(np.asarray(cache["tokens"][1, n:n + budget]), want)
    assert int(cache["start"][1]) == n // B * B + blocks * B
    # the slot that held no row did not move
    assert int(cache["start"][0]) == 0 and int(cache["pass"][0]) == 0


def test_the_schedule_splits_a_block_as_published():
    assert sdar.Schedule(4, 2, 9).transfers == (2, 2)
    assert sdar.Schedule(4, 3, 9).transfers == (2, 1, 1)
    assert sdar.Schedule(4, 4, 9).passes == 5
    assert ref.transfers(4, 3) == [2, 1, 1]
    with pytest.raises(ValueError, match="denoising"):
        sdar.Schedule(4, 5, 9)
    with pytest.raises(ValueError, match="dense"):
        sdar.SdarConfig.from_dict(dict(BB, mlp_only_layers=[0]))


def test_sampling_never_gives_the_mask_or_the_stop_unit():
    logits = jnp.asarray(np.random.default_rng(3).standard_normal((6, 512)),
                         jnp.float32)
    logits = logits.at[:, 510].set(60.0).at[:, 511].set(50.0)
    greedy = unit_layers.sample(logits, jnp.zeros((6,)), jax.random.PRNGKey(1),
                         UNITS)
    assert np.array_equal(np.asarray(greedy),
                          256 + np.asarray(logits)[:, 256:510].argmax(-1))
    drawn = unit_layers.sample(logits, jnp.full((6,), 5.0),
                               jax.random.PRNGKey(2), UNITS)
    assert 256 <= int(drawn.min()) and int(drawn.max()) < 510


def unmask_until_pr_46(logits, x, temperature, key, pass_no, schedule):
    """``sdar.unmask`` as PR 45 left it, over logits ``[S, B, V]``: the id
    of ``sample`` as it was and the confidence gathered from a
    ``log_softmax`` of the masked logits (``tools/profile_sampler.py``
    keeps that form to be measured against), then the ranking."""
    s, b = x.shape
    chosen, confidence, _ = profile_sampler.before(
        logits.reshape(s * b, -1), jnp.repeat(temperature, b), key, UNITS, b)
    masked = x == schedule.mask_id
    confidence = jnp.where(masked, confidence.reshape(s, b), -jnp.inf)
    order = jnp.argsort(-confidence, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    count = jnp.asarray(schedule.transfers + (0,), jnp.int32)[pass_no]
    taken = masked & (rank < count[:, None])
    return jnp.where(taken, chosen.reshape(s, b), x), taken


@pytest.mark.parametrize("temperature", [0.0, 0.667])
@pytest.mark.parametrize("seed", [3, 11, 46])
def test_unmask_unmasks_what_it_unmasked_before(seed, temperature):
    """``unmask`` over the head's ``[S * B, V]`` against the function it
    replaced over ``[S, B, V]``: the same positions unmasked with the same
    ids, greedy and (the key's bits are the same) drawn, for slots in every
    pass of the schedule and blocks that open with known tokens."""
    rng = np.random.default_rng(seed)
    slots, schedule = 5, sdar.Schedule(B, 2, UNITS.mask_id)
    logits = jnp.asarray(2.0 * rng.standard_normal((slots * B, 512)),
                         jnp.float32)
    x = jnp.asarray(np.where(rng.random((slots, B)) < 0.7, UNITS.mask_id,
                             rng.integers(256, 510, (slots, B))), jnp.int32)
    t = jnp.full((slots,), temperature, jnp.float32)
    pass_no = jnp.asarray(rng.integers(0, 3, (slots,)), jnp.int32)
    key = jax.random.PRNGKey(seed)
    after, taken = jax.jit(lambda *a: sdar.unmask(
        *a, units=UNITS, schedule=schedule))(logits, x, t, key, pass_no)
    want, want_taken = unmask_until_pr_46(
        logits.reshape(slots, B, -1), x, t, key, pass_no, schedule)
    assert np.array_equal(np.asarray(taken), np.asarray(want_taken))
    assert np.array_equal(np.asarray(after), np.asarray(want))
    assert taken.any() and not np.asarray(taken)[np.asarray(pass_no) == 2].any()
    assert not np.isin(np.asarray(after)[np.asarray(taken)],
                       [UNITS.mask_id, UNITS.stop_id]).any()


def test_the_new_fields_leave_the_lfm2_programs_as_they_were():
    """``router_scoring``, ``head_dim`` and ``tie_word_embeddings`` are read
    while a program is traced: an ``lfm2_moe`` configuration states none of
    them and gets the graph it had (a sigmoid router, the embedding's
    transpose as the head, no mask id in the sampler)."""
    tiny = json.loads((ROOT / "tests/perfbench/data/lfm2-tiny.json")
                      .read_text())
    cfg = lfm2.Lfm2Config.from_dict(lfm2gen.backbone(tiny))
    assert (cfg.head_dim, cfg.router_scoring, cfg.tie_word_embeddings) == (
        16, "sigmoid", True)
    units = unit_layers.UnitIds(256, 511)
    params = {"embed": jnp.zeros((512, 64)), "norm_f": jnp.ones((64,)),
              "layers": [lfm2.pack_layer(wide(lfm2gen.draw_layer(tiny, i)))
                         for i in range(6)]}

    def graph(cfg, units):
        return str(jax.make_jaxpr(lambda p, c: lfm2.step(
            p, c, jnp.ones((2,), bool), jnp.zeros((2,)), 0, cfg=cfg,
            units=units))(params, lfm2.new_cache(cfg, 2, 16)))

    plain = graph(cfg, units)
    assert plain == graph(dataclasses.replace(
        cfg, head_dim=16, router_scoring="sigmoid",
        tie_word_embeddings=True), unit_layers.UnitIds(256, 511, None))
    soft = graph(dataclasses.replace(cfg, router_scoring="softmax"), units)
    # a softmax in each of the four expert layers, in a sigmoid's place
    assert soft.count("reduce_max") == plain.count("reduce_max") + 4
    assert soft.count("logistic") == plain.count("logistic") - 4
    assert graph(cfg, unit_layers.UnitIds(256, 511, 300)) != plain
    with pytest.raises(KeyError, match="head"):
        graph(dataclasses.replace(cfg, tie_word_embeddings=False), units)
