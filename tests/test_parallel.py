"""Mesh / sharding / ring-attention tests on the 8-device virtual CPU mesh.

The reference has no distributed anything to mirror (SURVEY §5) — this
coverage is TPU-native by construction: batched synthesis sharded over the
data axis must produce the same audio as unsharded execution, and ring
attention must equal exact attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sonata_tpu.parallel import make_mesh, ring_attention
from sonata_tpu.models import PiperVoice

import voices
from voices import tiny_voice

# The 6 mesh-numeric equivalence tests in this file were xfailed between
# ISSUE 2 and ISSUE 3: a mesh pads the dispatch (batch rows up to a
# multiple of the data axis; 4 rows → 8 on make_mesh(8)) and the model
# used to draw duration/decoder noise with ONE per-dispatch PRNG key over
# batch-shaped tensors, so padded shapes changed every real row's draws
# relative to the unsharded dispatch.  Since `vits.per_row_normal`
# (per-row `fold_in(key, row)` keys over bucket-stable per-row shapes) a
# row's draw no longer depends on its batch neighbors or padding rows,
# and the sharded-vs-unsharded equivalence holds unconditionally.


def test_mesh_shapes():
    mesh = make_mesh(8)
    assert mesh.shape == {"data": 8, "seq": 1, "model": 1}
    mesh2 = make_mesh(8, seq_parallel=2)
    assert mesh2.shape == {"data": 4, "seq": 2, "model": 1}
    mesh3 = make_mesh(8, seq_parallel=2, model_parallel=2)
    assert mesh3.shape == {"data": 2, "seq": 2, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(6, seq_parallel=4)
    with pytest.raises(ValueError):
        make_mesh(8, seq_parallel=2, model_parallel=3)


def test_tensor_parallel_param_shardings():
    """The TP annotation shards exactly the decoder's conv channels:
    ups/resblock kernels on Cout, biases on C, conv_post and every
    non-decoder leaf replicated."""
    from jax.sharding import PartitionSpec as P

    from sonata_tpu.parallel import param_shardings

    mesh = make_mesh(8, model_parallel=2)
    v = tiny_voice(seed=30)
    sh = param_shardings(mesh, v.params)
    assert sh["dec"]["ups"][0]["w"].spec == P(None, None, "model")
    assert sh["dec"]["ups"][0]["b"].spec == P("model")
    assert sh["dec"]["resblocks"][0]["convs1"][0]["w"].spec == \
        P(None, None, "model")
    assert sh["dec"]["conv_post"]["w"].spec == P()  # 1 output channel
    assert sh["flow"]["layers"][0]["post"]["w"].spec == P()
    # non-decoder subtrees are fully replicated
    import jax.tree_util as jtu

    assert all(s.spec == P()
               for s in jtu.tree_leaves(sh["enc_p"]) +
               jtu.tree_leaves(sh["dp"]))


def test_tensor_parallel_streaming_matches_unsharded():
    """Streaming (stage coalescer + window decoders) on a dp+sp+tp mesh
    produces the same audio as a single device."""
    mesh = make_mesh(8, seq_parallel=2, model_parallel=2)
    v0 = tiny_voice(seed=32)
    vm = PiperVoice(v0.config, v0.params, seed=32, mesh=mesh)
    text = "wˈʌn tuː θɹiː fˈoːɹ."
    plain = np.concatenate(
        [c.samples.data for c in v0.stream_synthesis(text, 12, 2)])
    tp = np.concatenate(
        [c.samples.data for c in vm.stream_synthesis(text, 12, 2)])
    assert np.allclose(plain, tp, atol=2e-4)


def test_tensor_parallel_batch_matches_unsharded():
    """dp+sp+tp 3-axis mesh produces the same audio as a single device
    (the TP all-reduces are numerically transparent at f32 tolerance)."""
    import numpy as np

    mesh = make_mesh(8, seq_parallel=2, model_parallel=2)
    v_plain = tiny_voice(seed=31)
    v_mesh = PiperVoice(v_plain.config, v_plain.params, seed=31, mesh=mesh)
    batch = ["tɛst wʌn.", "tɛst tuː ɪz hɪɹ."]
    a_plain = v_plain.speak_batch(batch)
    a_mesh = v_mesh.speak_batch(batch)
    for ap, am in zip(a_plain, a_mesh):
        assert np.allclose(np.asarray(ap.samples.data),
                           np.asarray(am.samples.data), atol=2e-4)


def test_sharded_batch_matches_unsharded():
    mesh = make_mesh(8)
    v_plain = tiny_voice(seed=11)
    v_mesh = PiperVoice(v_plain.config, v_plain.params, seed=11, mesh=mesh)
    batch = ["tɛst wʌn.", "tɛst tuː ɪz hɪɹ.", "θɹiː.", "fɔːɹ moːɹ wɜːdz."]
    a_plain = v_plain.speak_batch(batch)
    a_mesh = v_mesh.speak_batch(batch)
    assert len(a_mesh) == 4
    for ap, am in zip(a_plain, a_mesh):
        # same seed, same RNG counter sequence → identical draws; sharding
        # must not change numerics beyond float reassociation
        assert len(ap.samples) == len(am.samples)
        np.testing.assert_allclose(ap.samples.data, am.samples.data,
                                   atol=2e-4)


def test_sharded_batch_covers_data_axis():
    mesh = make_mesh(8)
    v = tiny_voice(seed=3)
    vm = PiperVoice(v.config, v.params, seed=3, mesh=mesh)
    audios = vm.speak_batch(["tɛst."])  # 1 sentence → padded to 8 rows
    assert len(audios) == 1
    assert len(audios[0].samples) > 0
    assert {k[0] for k in vm._full_cache} == {8}


def _exact_attention(q, k, v, kv_valid):
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k)
    mask = jnp.where(kv_valid[:, None, None, :] > 0, 0.0, -1e9)
    w = jax.nn.softmax(logits + mask, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def test_ring_attention_matches_exact():
    mesh = make_mesh(8, seq_parallel=8)
    b, h, t, d = 2, 4, 64, 16
    rng = jax.random.PRNGKey(0)
    rq, rk, rv = jax.random.split(rng, 3)
    q = jax.random.normal(rq, (b, h, t, d))
    k = jax.random.normal(rk, (b, h, t, d))
    v = jax.random.normal(rv, (b, h, t, d))
    lengths = jnp.array([64, 40])

    out_ring = ring_attention(q, k, v, lengths, mesh)
    kv_valid = (jnp.arange(t)[None, :] < lengths[:, None]).astype(q.dtype)
    out_exact = _exact_attention(q, k, v, kv_valid)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_exact),
                               atol=2e-5)


def test_ring_attention_jits_and_shards():
    mesh = make_mesh(8, seq_parallel=4)
    b, h, t, d = 1, 2, 32, 8
    q = jnp.ones((b, h, t, d))
    lengths = jnp.array([t])
    f = jax.jit(lambda q: ring_attention(q, q, q, lengths, mesh))
    out = f(q)
    assert out.shape == (b, h, t, d)
    assert bool(jnp.isfinite(out).all())


def test_streaming_with_mesh_ignores_dummy_rows():
    mesh = make_mesh(8)
    v = tiny_voice(seed=5)
    vm = PiperVoice(v.config, v.params, seed=5, mesh=mesh)
    ph = "ə sɛntəns fɔːɹ stɹiːmɪŋ tɛsts."
    plain = sum(len(c.samples) for c in v.stream_synthesis(ph, 15, 2))
    meshed = sum(len(c.samples) for c in vm.stream_synthesis(ph, 15, 2))
    # same seed and call order → same durations; dummy rows must not add
    # frames
    assert meshed == plain


def test_non_power_of_two_mesh():
    mesh = make_mesh(6)
    v = tiny_voice(seed=2)
    vm = PiperVoice(v.config, v.params, seed=2, mesh=mesh)
    audios = vm.speak_batch(["tɛst wʌn.", "tuː.", "θɹiː.", "fɔːɹ.", "faɪv."])
    assert len(audios) == 5
    assert all(len(a.samples) > 0 for a in audios)


def test_ring_attention_custom_axis():
    mesh = make_mesh(8)  # data=8, seq=1
    b, h, t, d = 1, 2, 32, 8
    q = jax.random.normal(jax.random.PRNGKey(2), (b, h, t, d))
    lengths = jnp.array([t])
    out = ring_attention(q, q, q, lengths, mesh, axis_name="data")
    kv_valid = jnp.ones((b, t))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_exact_attention(q, q, q, kv_valid)),
                               atol=2e-5)


def test_orbax_sharded_checkpoint_roundtrip(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from sonata_tpu.parallel import checkpoint

    v = tiny_voice(seed=17)
    path = tmp_path / "ckpt"
    checkpoint.save(path, v.params)
    back = checkpoint.restore(path, like=v.params)
    from sonata_tpu.models.serialization import flatten_params

    fa, fb = flatten_params(v.params), flatten_params(back)
    assert fa.keys() == fb.keys()
    for k in fa:
        np.testing.assert_array_equal(fa[k], fb[k])


def test_orbax_restore_missing_path(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from sonata_tpu.core import FailedToLoadResource
    from sonata_tpu.parallel import checkpoint

    with pytest.raises(FailedToLoadResource):
        checkpoint.restore(tmp_path / "nope")


# ---------------------------------------------------------------------------
# sequence parallelism in the serving path (ring-attention text encoder)
# ---------------------------------------------------------------------------

def test_seq_parallel_transformer_matches_baseline():
    from sonata_tpu.models import modules as m

    C, H, W, L = 32, 2, 4, 2
    p = m.init_transformer(jax.random.PRNGKey(0), channels=C,
                           filter_channels=64, n_heads=H, n_layers=L,
                           kernel=3, window=W)
    B, T = 4, 48
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, C))
    lengths = jnp.array([48, 31, 7, 20])
    mask = (jnp.arange(T)[None, :] <
            lengths[:, None]).astype(jnp.float32)[..., None]
    base = m.transformer(x, mask, p, n_heads=H, window=W)
    # seq=4 exercises multi-hop ring passes; seq=2 is a strict subset of
    # the same code path and compiling both nearly doubles this test's
    # (compile-dominated) cost
    for seq in (4,):
        mesh = make_mesh(8, seq_parallel=seq)
        out = m.transformer_seq_parallel(x, mask, p, n_heads=H, window=W,
                                         mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=2e-5)


def test_seq_parallel_batch_matches_unsharded(monkeypatch):
    """speak_batch on a seq_parallel=2 mesh produces the same audio as the
    single-device path — and the encoder really goes through the ring
    (spied at trace time, so this can't silently revert to the unsharded
    transformer)."""
    from sonata_tpu.models import modules as mmod

    calls = []
    orig = mmod.transformer_seq_parallel

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(mmod, "transformer_seq_parallel", spy)
    mesh = make_mesh(8, seq_parallel=2)
    v_plain = tiny_voice(seed=11)
    v_mesh = PiperVoice(v_plain.config, v_plain.params, seed=11, mesh=mesh)
    batch = ["tɛst wʌn.", "tɛst tuː ɪz hɪɹ.", "θɹiː.", "fɔːɹ moːɹ wɜːdz."]
    a_plain = v_plain.speak_batch(batch)
    assert not calls  # unsharded path must not ring
    a_mesh = v_mesh.speak_batch(batch)
    assert calls  # sharded path traced through the ring encoder
    for ap, am in zip(a_plain, a_mesh):
        assert len(ap.samples) == len(am.samples)
        np.testing.assert_allclose(ap.samples.data, am.samples.data,
                                   atol=2e-4)


def test_seq_parallel_encode_executes_ppermute():
    """The compiled encode stage must contain collective-permute ops when
    the mesh has a seq axis — sequence parallelism is a serving feature,
    not demo-ware."""
    mesh = make_mesh(8, seq_parallel=2)
    v = tiny_voice(seed=1)
    vm = PiperVoice(v.config, v.params, seed=1, mesh=mesh)
    fn = vm._encode_fn(8, 32)  # batch 8, text bucket 32 (divisible by 2)
    ids = jnp.zeros((8, 32), jnp.int32)
    lens = jnp.full((8,), 32, jnp.int32)
    lowered = fn.lower(vm.params, ids, lens, jax.random.PRNGKey(0),
                       jnp.ones((8,)), jnp.ones((8,)))
    hlo = lowered.compile().as_text()
    assert "collective-permute" in hlo


def test_frame_domain_seq_parallel_matches_unsharded():
    """Flow reverse + HiFi-GAN decode sharded over frames equal the
    unsharded ops (halo-exchange convs; transposed-conv halos)."""
    from sonata_tpu.models import vits
    from sonata_tpu.models.seq_parallel import decode_sp, flow_reverse_sp

    v = tiny_voice(seed=2)
    hp, p = v.hp, v.params
    F = 64
    # seq=4 covers the smallest per-shard frame count (tightest halo
    # margin); the seq=2 variant compiles the same code for little gain
    for seq in (4,):
        mesh = make_mesh(8, seq_parallel=seq)
        B = mesh.shape["data"]
        z = jax.random.normal(jax.random.PRNGKey(0),
                              (B, F, hp.inter_channels))
        lengths = jnp.arange(B) * 7 % F + 8
        mask = (jnp.arange(F)[None, :] <
                lengths[:, None]).astype(jnp.float32)[..., None]
        np.testing.assert_allclose(
            np.asarray(flow_reverse_sp(p["flow"], hp, z, mask, mesh)),
            np.asarray(vits.flow_reverse(p["flow"], hp, z, mask)),
            atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(decode_sp(p, hp, z, mesh)),
            np.asarray(vits.decode(p, hp, z)), atol=2e-5)


def test_min_local_frames_counts_the_folded_halo():
    """A time-folded stage exchanges whole folded steps.  Kernel 11 at
    dilation 5 reaches 25 samples; at 32 channels (four steps a fold) that
    is 7 folded steps = 28 samples, and one spare: 29 samples at twice the
    frame rate = 15 frames (13 unfolded).  The decode sharded at 16, the
    smallest count above it whose shard still folds, equals the unsharded
    one."""
    from sonata_tpu.models import vits
    from sonata_tpu.models.seq_parallel import decode_sp, min_local_frames

    v = tiny_voice(seed=4, model=dict(
        voices.TINY_MODEL, upsample_rates=(2, 2),
        upsample_kernel_sizes=(4, 4), resblock_kernel_sizes=(11,),
        resblock_dilation_sizes=((1, 5),)))
    hp, p = v.hp, v.params
    assert min_local_frames(hp) == 15
    assert min_local_frames(tiny_voice().hp) == 4    # conv_pre's, as before
    mesh = make_mesh(8, seq_parallel=2)
    assert not vits._use_seq_parallel(mesh, 28, hp)
    assert vits._use_seq_parallel(mesh, 32, hp)
    assert vits.decode_fold(p["dec"], hp, 32, mesh) == [4, 8]
    z = jax.random.normal(jax.random.PRNGKey(0),
                          (mesh.shape["data"], 32, hp.inter_channels))
    np.testing.assert_allclose(np.asarray(decode_sp(p, hp, z, mesh)),
                               np.asarray(vits.decode(p, hp, z)), atol=2e-5)


def test_full_batch_hlo_shards_frame_domain():
    """With a seq axis, the compiled full pipeline contains
    collective-permutes from BOTH the ring encoder and the frame-domain
    halo exchanges (flow + decoder)."""
    mesh = make_mesh(8, seq_parallel=2)
    v = tiny_voice(seed=1)
    vm = PiperVoice(v.config, v.params, seed=1, mesh=mesh)
    fn = vm._full_fn(8, 32, 128)
    ids = jnp.zeros((8, 32), jnp.int32)
    lens = jnp.full((8,), 32, jnp.int32)
    ones = jnp.ones((8,))
    lowered = fn.lower(vm.params, ids, lens, jax.random.PRNGKey(0),
                       ones, ones, ones)
    hlo = lowered.compile().as_text()
    assert hlo.count("collective-permute") >= 4


def test_long_utterance_spans_seq_shards():
    """A genuinely long utterance (frame bucket >= 256 ⇒ 128 frames per
    shard at seq=2) produces identical audio sharded vs unsharded — the
    long-context path, with the latent and waveform split across chips."""
    mesh = make_mesh(8, seq_parallel=2)
    v_plain = tiny_voice(seed=23)
    v_mesh = PiperVoice(v_plain.config, v_plain.params, seed=23, mesh=mesh)
    long_text = " ".join(["wʌn tuː θɹiː fɔːɹ faɪv sɪks"] * 8) + "."
    a_plain = v_plain.speak_batch([long_text])
    a_mesh = v_mesh.speak_batch([long_text])
    assert len(a_plain[0].samples) == len(a_mesh[0].samples)
    assert len(a_plain[0].samples) > 3000  # actually long
    np.testing.assert_allclose(a_plain[0].samples.data,
                               a_mesh[0].samples.data, atol=2e-4)


def test_decode_sp_bfloat16_close_to_unsharded_bf16():
    """The reduced-precision policy threads through the seq-parallel
    decoder (halo exchanges ride bfloat16): sharded-bf16 must match
    unsharded-bf16 exactly (same ops), and sit near float32."""
    import jax.numpy as jnp

    from sonata_tpu.models import vits
    from sonata_tpu.models.seq_parallel import decode_sp

    v = tiny_voice(seed=3)
    hp, p = v.hp, v.params
    F = 64
    mesh = make_mesh(8, seq_parallel=2)
    B = mesh.shape["data"]
    z = jax.random.normal(jax.random.PRNGKey(1), (B, F, hp.inter_channels))
    sharded = np.asarray(decode_sp(p, hp, z, mesh,
                                   compute_dtype=jnp.bfloat16))
    unsharded = np.asarray(vits.decode(p, hp, z,
                                       compute_dtype=jnp.bfloat16))
    np.testing.assert_allclose(sharded, unsharded, atol=2e-5)
    assert np.isfinite(sharded).all()
    # (bf16-vs-f32 closeness is pinned on the unsharded path in
    # test_vits_model.py::test_bfloat16_decode_close_to_float32; skipping
    # the extra f32 compile here keeps the suite compile budget down)
