"""The plain reference against the program's own graph, stage by stage, at
the tiny size, on seeded weights made by the benchmark."""

import json
import random
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module", params=["tiny", "tiny-multi"])
def setup(request):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import textgen, voicegen

    config = json.loads((DATA / f"{request.param}.json").read_text())
    voice = config["voice"]
    flat = voicegen.build_params(voice, **config["weights"])
    params = jax.tree_util.tree_map(jnp.asarray, voicegen.unflatten(flat))
    lexicon = textgen.Lexicon(ROOT / "perfbench/traffic/words.tsv")
    rng = random.Random(3)
    rows = [textgen.text_to_ids(lexicon, lexicon.sentence(n, rng),
                                voice["phoneme_id_map"])
            for n in (12, 20, 16)]
    ids = np.zeros((3, 64), np.int32)
    for k, r in enumerate(rows):
        ids[k, :len(r)] = r
    lens = np.array([len(r) for r in rows], np.int32)
    sid = (jnp.array([1, 3, 0], jnp.int32)
           if voice["num_speakers"] > 1 else None)
    return voice, params, ids, lens, sid


def test_durations_priors_and_waveform_agree_with_the_program(setup):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import voicegen
    from perfbench.reference import vits_ref as ref
    from sonata_tpu.models import vits
    from sonata_tpu.models.config import ModelConfig

    voice, params, ids, lens, sid = setup
    dims = voicegen.model_dims(voice)
    hp = ModelConfig.from_dict(voice).hyper
    key = jax.random.PRNGKey(0)
    m_p, logs_p, w_ceil, x_mask, g = vits.encode_text(
        params, hp, ids, lens, key, noise_w=0.0, length_scale=1.0, sid=sid)
    r_m, r_logs, r_w = ref.encode(params, dims, ids, lens, sid)
    np.testing.assert_allclose(np.asarray(r_m), np.asarray(m_p), atol=2e-5)
    np.testing.assert_allclose(np.asarray(r_logs), np.asarray(logs_p),
                               atol=2e-5)
    assert np.array_equal(np.ceil(np.asarray(r_w)), np.asarray(w_ceil))
    assert float(np.asarray(w_ceil).sum()) > 3 * 40   # durations are alive

    frames = 256
    z, y_mask, y_len = vits.acoustics(
        params, hp, m_p, logs_p, w_ceil, x_mask, key, noise_scale=0.0,
        max_frames=frames, g=g)
    want = np.asarray(vits.decode(params, hp, z, g=g))
    got, r_len = ref.synthesize(params, dims, r_m, r_logs,
                                jnp.ceil(r_w).astype(jnp.int32), frames, sid)
    assert np.array_equal(np.asarray(r_len), np.asarray(y_len))
    for k in range(3):
        n = int(y_len[k]) * ref.hop_length(dims)
        assert np.std(want[k, :n]) > 0.02          # the waveform is alive
        np.testing.assert_allclose(np.asarray(got)[k, :n], want[k, :n],
                                   atol=1e-4)


def test_candidates_recover_a_moved_duration():
    from perfbench.reference.vits_check import candidates

    w = np.array([2.5, 2.999, 3.4, 1.2, 0.0])
    base = np.ceil(w[:4]).astype(int)
    assert np.array_equal(candidates(w, 4, int(base.sum()))[0], base)
    moved = candidates(w, 4, int(base.sum()) + 1)[0]
    assert moved[1] == 4 and moved.sum() == base.sum() + 1
    assert candidates(w, 4, int(base.sum()) + 9) == []
