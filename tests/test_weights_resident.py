"""A voice's weights live on the device from load: a ``PiperVoice`` built
from an ``.npz`` on disk (the tests' usual ``PiperVoice.random`` voice
holds device arrays from ``init_vits`` already, so it never showed the
upload) places its tree once, under the placement its programs expect,
and no program call hands it over again."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from sonata_tpu.models import PiperVoice, from_config_path, shape_plan
from sonata_tpu.models.decode_opts import decoder_is_quantized
from sonata_tpu.models.serialization import load_params
from sonata_tpu.parallel import make_mesh
from sonata_tpu.parallel.mesh import param_shardings
from sonata_tpu.serving import tracing
from sonata_tpu.utils.buckets import FRAME_BUCKETS, bucket_for

from voices import write_tiny_voice

SENTENCES = ["Hello world.", "This is a longer sentence for the test.",
             "Short."]
MESHES = {"data8": dict(n_devices=8),
          "data4_model2": dict(n_devices=8, model_parallel=2)}


@pytest.fixture(scope="module")
def voice_path(tmp_path_factory):
    return write_tiny_voice(tmp_path_factory.mktemp("npz_voice"), seed=5)


def leaves(voice):
    return jax.tree_util.tree_leaves(voice.params)


def phonemes(voice):
    return [p for s in SENTENCES for p in voice.phonemize_text(s)]


def device_groups(voice, phoneme_batches):
    """The program records of one ``speak_batch``."""
    tracer = tracing.Tracer(enabled=True, log_sink="0")
    with tracer.trace_request("req", request_id="resident") as trace:
        voice.speak_batch(phoneme_batches)
    (span,) = [s for s in trace.spans_snapshot() if s.name == "dispatch"]
    return span.attrs["device_groups"]


def test_npz_voice_holds_every_leaf_on_the_default_device(voice_path):
    host = jax.tree_util.tree_leaves(
        load_params(voice_path.with_name("voice.npz")))
    assert all(isinstance(leaf, np.ndarray) for leaf in host)
    voice = from_config_path(voice_path)
    assert isinstance(voice, PiperVoice)
    placed = leaves(voice)
    assert len(placed) == len(host)
    for leaf, was in zip(placed, host):
        assert isinstance(leaf, jax.Array)
        assert leaf.devices() == {jax.devices()[0]}
        assert leaf.dtype == was.dtype and leaf.shape == was.shape
    assert voice._weights_host_bytes == 0


@pytest.mark.parametrize("mesh_kw", MESHES.values(), ids=MESHES.keys())
def test_mesh_voice_holds_every_leaf_under_its_param_sharding(voice_path,
                                                              mesh_kw):
    mesh = make_mesh(**mesh_kw)
    voice = from_config_path(voice_path, mesh=mesh)
    want = jax.tree_util.tree_leaves(param_shardings(mesh, voice.params))
    placed = leaves(voice)
    assert len(placed) == len(want)
    for leaf, sharding in zip(placed, want):
        assert isinstance(leaf, jax.Array)
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
    if mesh_kw.get("model_parallel", 1) > 1:    # not all merely replicated
        assert any(not leaf.sharding.is_fully_replicated for leaf in placed)
    assert voice._weights_host_bytes == 0


def test_int8_arm_is_quantised_then_placed(voice_path):
    voice = from_config_path(voice_path, decode_quant="int8")
    assert decoder_is_quantized(voice.params["dec"])
    placed = leaves(voice)
    assert all(isinstance(leaf, jax.Array) for leaf in placed)
    assert any(leaf.dtype == np.int8 for leaf in placed)
    assert voice._weights_host_bytes == 0
    assert len(voice.speak_batch(phonemes(voice))) == len(SENTENCES)


def test_replica_holds_every_leaf_on_its_device(voice_path):
    voice = from_config_path(voice_path)
    device = jax.devices()[3]
    replica = voice.replica_for_device(device, seed_offset=3)
    for leaf in leaves(replica):
        assert isinstance(leaf, jax.Array) and leaf.committed
        assert leaf.devices() == {device}
    assert replica._weights_host_bytes == 0
    # the template's own tree stayed where it was
    assert all(leaf.devices() == {jax.devices()[0]}
               for leaf in leaves(voice))


def test_speak_batch_is_bit_identical_to_the_numpy_tree_call(voice_path):
    """The program called the old way, with the tree ``np.load`` gave as
    argument 0, on the arguments a twin voice's enqueue made."""
    voice = from_config_path(voice_path, seed=9)
    twin = from_config_path(voice_path, seed=9)
    host_tree = load_params(voice_path.with_name("voice.npz"))
    batch = phonemes(voice)
    audios = voice.speak_batch(batch)

    ids = [twin._encode_phonemes(p) for p in batch]
    sc = twin.get_fallback_synthesis_config()
    groups = shape_plan.plan_dispatch_groups(
        [len(i) for i in ids], [sc.length_scale] * len(ids),
        min_batch=twin.MIN_DISPATCH_BATCH, max_batch=twin.MAX_DISPATCH_BATCH)
    # the twin walks speak_batch's order: with two groups at most, each
    # is enqueued after the one before it was fetched
    assert 1 <= len(groups) <= 2
    for chunk in groups:
        ticket = twin._enqueue_batch([ids[i] for i in chunk], sc)
        b, t, f = ticket["b"], ticket["t"], ticket["f"]
        old = jax.device_get(
            twin._full_fn(b, t, f)(host_tree, *ticket["args"][1:]))
        for got, want in zip(jax.device_get(ticket["out"]), old):
            assert np.array_equal(got, want)
        twin._finish_batch(ticket)      # the estimator sees what it saw
        needed = int(old[3][:len(chunk)].max())
        if needed > f:              # clipped: the rerun, as _finish_batch's
            old = jax.device_get(twin._full_fn(
                b, t, bucket_for(needed, FRAME_BUCKETS))(
                    host_tree, *ticket["args"][1:]))
        wav_i16, wav_lengths, peaks, _ = old
        for row, i in enumerate(chunk):
            want = (wav_i16[row].astype(np.float32)
                    * (np.maximum(peaks[row], 0.01) / 32767.0)
                    )[:int(wav_lengths[row])]
            assert np.array_equal(np.asarray(audios[i].samples.data), want)


def guard_program_calls(voice, monkeypatch):
    """Every full-pipeline program of ``voice`` is called with implicit
    host-to-device transfers refused."""
    full_fn = voice._full_fn

    def guarded_fn(b, t, f):
        fn = full_fn(b, t, f)

        def call(*args):
            with jax.transfer_guard_host_to_device("disallow"):
                return fn(*args)

        return call

    monkeypatch.setattr(voice, "_full_fn", guarded_fn)


def test_program_calls_pass_a_guard_that_refuses_host_weights(voice_path,
                                                              monkeypatch):
    voice = from_config_path(voice_path)
    guard_program_calls(voice, monkeypatch)
    batch = phonemes(voice)
    assert len(voice.speak_batch(batch)) == len(batch)
    # the same calls with the tree np.load gave: refused, where the
    # backend honours the guard at all
    voice.params = load_params(voice_path.with_name("voice.npz"))
    try:
        voice.speak_batch(batch)
    except Exception as e:
        assert "host-to-device" in str(e)
    else:
        pytest.skip("this backend does not honour the transfer guard")


@pytest.mark.parametrize("mesh_kw", MESHES.values(), ids=MESHES.keys())
def test_a_mesh_dispatch_uploads_kilobytes(voice_path, mesh_kw):
    """As ``test_dispatch_record`` reads it without a mesh: the tree is
    not handed over (nor sharded again) per dispatch."""
    weights = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
        load_params(voice_path.with_name("voice.npz"))))
    assert weights > 64 * 1024      # the bar below tells the tree apart
    voice = from_config_path(voice_path, mesh=make_mesh(**mesh_kw))
    groups = device_groups(voice, phonemes(voice))
    assert groups
    for g in groups:
        assert g["upload_bytes"] == (
            g["batch_bucket"] * (g["text_bucket"] + 4) * 4 + 8)
        assert g["upload_bytes"] < 64 * 1024
