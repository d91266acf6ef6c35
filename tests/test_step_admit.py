"""A step that carries an arrival (``lfm2.step_admit``,
``nemotron_h.step_admit``, ``pangu_moe.step_admit``, ``laguna.step_admit``,
``gigachat.step_admit``) against the two programs it stands for, at a tiny
size on the CPU, float32: from one cache with some slots live, the carrying
form with a prompt for slot ``s`` gives the cache, the live rows' logits and
the prompt's logits that ``step`` (with ``s`` not live) followed by
``prefill`` into ``s`` give; and which backbone offers the form, for which
prompts."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import gigachatgen, lagunagen, lfm2gen, nemotrongen, \
    pangugen
from sonata_tpu.models import gigachat, laguna, lfm2, nemotron_h, pangu_moe, \
    unit_layers, unit_voice

ROOT = Path(__file__).resolve().parent.parent
DATA = ROOT / "tests/perfbench/data"
UNITS = unit_layers.UnitIds(256, 511)
SLOTS, POSITIONS = 4, 64
#: the arriving row's slot: it held a row before (its state is stale)
SLOT = 2


def wide(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture
def float32_products(monkeypatch):
    """The products take float32 at ``highest``, so that two orders of the
    same sums can be held to each other to rounding."""
    monkeypatch.setattr(unit_layers, "BF16", jnp.float32)
    monkeypatch.setattr(lfm2, "BF16", jnp.float32)
    monkeypatch.setattr(nemotron_h, "BF16", jnp.float32)
    monkeypatch.setattr(pangu_moe, "BF16", jnp.float32)
    monkeypatch.setattr(laguna, "BF16", jnp.float32)
    monkeypatch.setattr(gigachat, "BF16", jnp.float32)
    with jax.default_matmul_precision("highest"):
        yield


def lfm2_backbone():
    config = json.loads((DATA / "lfm2-tiny.json").read_text())
    cfg = lfm2.Lfm2Config.from_dict(lfm2gen.backbone(config))
    params = {"embed": wide(lfm2gen.draw(config, "embed")),
              "norm_f": wide(lfm2gen.draw(config, "norm_f")),
              "layers": [lfm2.pack_layer(wide(lfm2gen.draw_layer(config, i)))
                         for i in range(len(cfg.layer_types))]}
    return lfm2, cfg, params


def nemotron_backbone():
    config = json.loads((DATA / "nemotron-tiny.json").read_text())
    cfg = nemotron_h.NemotronConfig.from_dict(nemotrongen.backbone(config))
    params = {"embed": wide(nemotrongen.draw(config, "embed")),
              "head": wide(nemotrongen.draw(config, "head")),
              "norm_f": wide(nemotrongen.draw(config, "norm_f")),
              "layers": [nemotron_h.pack_layer(wide(
                  nemotrongen.draw_layer(config, i)))
                  for i in range(len(cfg.pattern))]}
    return nemotron_h, cfg, params


def pangu_backbone():
    config = json.loads((DATA / "pangu-tiny.json").read_text())
    cfg = pangu_moe.PanguConfig.from_dict(pangugen.backbone(config))
    params = {"embed": wide(pangugen.draw(config, "embed")),
              "head": wide(pangugen.draw(config, "head")),
              "norm_f": wide(pangugen.draw(config, "norm_f")),
              "layers": [pangu_moe.pack_layer(wide(
                  pangugen.draw_layer(config, i)), cfg)
                  for i in range(cfg.num_hidden_layers)]}
    return pangu_moe, cfg, params


def laguna_backbone():
    """A window of 8 places: the rows in use have wrapped their rings, and
    the longer prompts are longer than the window."""
    config = json.loads((DATA / "laguna-tiny.json").read_text())
    cfg = laguna.LagunaConfig.from_dict(lagunagen.backbone(config))
    params = {"embed": wide(lagunagen.draw(config, "embed")),
              "head": wide(lagunagen.draw(config, "head")),
              "norm_f": wide(lagunagen.draw(config, "norm_f")),
              "layers": [laguna.pack_layer(wide(
                  lagunagen.draw_layer(config, i)))
                  for i in range(len(cfg.layer_types))]}
    return laguna, cfg, params


def gigachat_backbone():
    """A delta-rule state a head in the linear layers, latent rows in the
    full one: the prompt's chunked form writes the slot's state whole."""
    config = json.loads((DATA / "gigachat-tiny.json").read_text())
    cfg = gigachat.GigaChatConfig.from_dict(gigachatgen.backbone(config))
    params = {"embed": wide(gigachatgen.draw(config, "embed")),
              "head": wide(gigachatgen.draw(config, "head")),
              "norm_f": wide(gigachatgen.draw(config, "norm_f")),
              "layers": [gigachat.pack_layer(wide(
                  gigachatgen.draw_layer(config, i)), cfg)
                  for i in range(cfg.num_hidden_layers)]}
    return gigachat, cfg, params


BACKBONES = {"lfm2_moe": lfm2_backbone, "nemotron_h": nemotron_backbone,
             "pangu_ultra_moe": pangu_backbone, "laguna": laguna_backbone,
             "gigachat3_5": gigachat_backbone}


@pytest.fixture(scope="module", params=sorted(BACKBONES))
def backbone(request):
    return BACKBONES[request.param]()


def prompt(n: int, bucket: int, seed: int):
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = np.random.default_rng(seed).integers(0, 256, size=(n,))
    return jnp.asarray(padded)


def a_cache_in_use(mod, cfg, params):
    """Every slot has held a row: prompts of 5-11 ids and three steps of
    all four, so that the arriving slot's state is a stale row's."""
    cache = mod.new_cache(cfg, SLOTS, POSITIONS)
    for slot in range(SLOTS):
        cache, _, _ = mod.prefill(
            params, cache, prompt(5 + 2 * slot, 16, slot), 5 + 2 * slot, slot,
            jnp.float32(0.0), jax.random.PRNGKey(slot), cfg=cfg, units=UNITS)
    for k in range(3):
        cache, _, _ = mod.step(params, cache, jnp.ones((SLOTS,), bool),
                               jnp.zeros((SLOTS,)), k, cfg=cfg, units=UNITS)
    return cache


@pytest.mark.parametrize("n,bucket,live", [
    (11, 16, (True, True, False, True)),
    (23, 32, (True, False, False, True)),
    (16, 16, (False, False, False, False)),
], ids=["bucket-16", "bucket-32", "no-live-row"])
def test_a_carrying_step_is_a_step_and_then_a_prefill(
        backbone, n, bucket, live, float32_products):
    mod, cfg, params = backbone
    cache = a_cache_in_use(mod, cfg, params)
    live = jnp.asarray(live)
    # sampled, so that the keys of both kinds of row are held too
    temperature = jnp.full((SLOTS,), 0.7)
    ids, key = prompt(n, bucket, 40 + n), jax.random.PRNGKey(9)
    stale = jax.tree_util.tree_map(np.asarray, cache)

    want, want_logits, load_s = mod.step(
        params, cache, live, temperature, 5, cfg=cfg, units=UNITS, seed=3)
    want, want_first, load_p = mod.prefill(
        params, want, ids, n, SLOT, jnp.float32(0.7), key, cfg=cfg,
        units=UNITS)
    got, both, load = mod.step_admit(
        params, cache, live, temperature, 5, ids, n, SLOT, jnp.float32(0.7),
        key, cfg=cfg, units=UNITS, seed=3)
    assert both.shape == (SLOTS + 1, want_logits.shape[1])
    logits, first = both[:SLOTS], both[SLOTS]

    np.testing.assert_allclose(logits, want_logits, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(first, want_first, atol=2e-5, rtol=2e-5)
    flat_got, tree = jax.tree_util.tree_flatten(got)
    flat_want, tree_want = jax.tree_util.tree_flatten(want)
    assert tree == tree_want
    for a, b in zip(flat_got, flat_want):
        assert a.dtype == b.dtype and a.shape == b.shape
        if jnp.issubdtype(a.dtype, jnp.integer):
            assert np.array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    # the launch's load is both kinds of row together: assignments add up
    # (columns 2, and 4 where the chip holds a share), distinct experts do
    # not
    load, both = np.asarray(load), np.asarray(load_s) + np.asarray(load_p)
    assert np.array_equal(load[:, 2::2], both[:, 2::2])
    assert (load[:, 0] <= both[:, 0]).all()
    assert (load[:, 0] >= np.maximum(np.asarray(load_s)[:, 0],
                                     np.asarray(load_p)[:, 0])).all()
    # the prompt's write is the last word on the slot: nothing of the stale
    # row is left where the prompt wrote, and the row starts at its first unit
    assert int(got["pos"][SLOT]) == n and int(got["count"][SLOT]) == 1
    assert int(got["token"][SLOT]) == int(got["units"][SLOT, 0]) >= 256
    for name in ("k", "v", "latent"):
        for new, old in zip(got.get(name, ()), stale.get(name, ())):
            assert not np.array_equal(new[SLOT, :n], old[SLOT, :n])
    # and the slots that were not live stand where they stood
    for slot in range(SLOTS):
        if slot != SLOT and not bool(live[slot]):
            assert int(got["pos"][slot]) == int(stale["pos"][slot])
            assert int(got["count"][slot]) == int(stale["count"][slot])


class Sized:
    """What ``UnitVoice.carries`` reads of a voice."""

    slots = 64
    carries = unit_voice.UnitVoice.carries

    def __init__(self, backbone, step_impl):
        self.backbone = backbone
        self.cfg = backbone.cfg
        self.expert_matmul = step_impl


@pytest.mark.parametrize("config,slots", [
    ("lfm2/lfm2-24b-a2b.json", 64),
    ("nemotron/nemotron-3-nano-30b-a3b.json", 256),
    ("sdar/sdar-30b-a3b.json", 64),
    ("pangu/openpangu-ultra-moe-718b.json", 256),
    ("laguna/laguna-xs.2.json", 256),
    ("gigachat/gigachat3.5-432b-a28b.json", 256),
], ids=["lfm2_moe", "nemotron_h", "sdar_moe", "pangu_ultra_moe", "laguna",
        "gigachat3_5"])
def test_which_rows_ride_a_step_is_the_backbones_and_the_shapes_to_say(
        config, slots, monkeypatch):
    from sonata_tpu.ops import grouped_matmul
    from sonata_tpu.utils.buckets import TEXT_BUCKETS

    data = json.loads((ROOT / "perfbench/configs" / config).read_text())
    gen = {"lfm2": lfm2gen, "nemotron": nemotrongen, "pangu": pangugen,
           "laguna": lagunagen,
           "gigachat": gigachatgen}.get(config.split("/")[0])
    if gen is None:
        from perfbench.harness import sdargen as gen
    units = {"first_id": 256, "stop_id": 511, "mask_id": 300,
             "block_length": 4}
    built = unit_voice.make_backbone(gen.backbone(data), units)
    # as on a TPU: the kernel's tile rule decides
    monkeypatch.setattr(grouped_matmul, "_tiles_here", grouped_matmul.tile_rule)
    step_impl = unit_layers.expert_matmul(
        built.cfg, slots * built.block_length, built.held)
    assert step_impl == "grouped"
    voice = Sized(built, step_impl)
    voice.slots = slots
    rides = [voice.carries(t) for t in TEXT_BUCKETS]
    if config.startswith("sdar"):
        assert not any(rides)       # the backbone builds no such step
        return
    # every text bucket of the cells' lattices rides, on the kernel
    assert all(rides)
    assert {unit_layers.expert_matmul(built.cfg, slots + t, built.held)
            for t in TEXT_BUCKETS} == {"grouped"}
    # a step whose own products the rule leaves to ragged_dot loses nothing
    # by a prompt; one on the kernel does not give it up for one
    # (a thin share's short path has the step's own 256 rows up to a text
    # bucket of 128: those ride either way)
    step_rows = unit_layers.held_rows(built.cfg, slots * built.block_length,
                               built.held)
    monkeypatch.setattr(grouped_matmul, "_tiles_here",
                        lambda rows, *a: grouped_matmul.tile_rule(rows, *a)
                        if rows <= step_rows else None)
    more = [t for t in TEXT_BUCKETS
            if unit_layers.held_rows(built.cfg, slots + t, built.held)
            > step_rows]
    assert {192, 256} <= set(more)
    assert not any(voice.carries(t) for t in more)
    voice.expert_matmul = "ragged_dot"
    assert all(voice.carries(t) for t in more)
