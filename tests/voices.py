"""Shared test fixtures: tiny randomly-initialized voices.

The reference's e2e tier needs real voice files a developer must download
(``synth/models/.gitignore``, SURVEY §4) — its suite cannot run hermetically.
Ours can: a structurally-complete VITS with tiny dims exercises every code
path (jit, bucketing, streaming, speakers) in seconds on CPU.
"""

from sonata_tpu.models import PiperVoice

# Small enough to compile fast on a 1-core CPU runner; structurally complete.
TINY_MODEL = dict(
    inter_channels=32,
    hidden_channels=32,
    filter_channels=64,
    n_heads=2,
    n_layers=2,
    upsample_rates=(4, 4),
    upsample_initial_channel=64,
    upsample_kernel_sizes=(8, 8),
    resblock_kernel_sizes=(3,),
    resblock_dilation_sizes=((1, 3),),
    dp_filter_channels=32,
    gin_channels=16,
    flow_n_layers=2,
    flow_wn_layers=2,
)


def tiny_voice(seed: int = 0, **overrides) -> PiperVoice:
    kw = {
        "model": dict(TINY_MODEL),
        "audio": {"sample_rate": 16000, "quality": None},
    }
    kw.update(overrides)
    return PiperVoice.random(seed=seed, **kw)


def tiny_multispeaker_voice(n: int = 4, seed: int = 0) -> PiperVoice:
    return tiny_voice(
        seed=seed,
        num_speakers=n,
        speaker_id_map={f"spk{i}": i for i in range(n)},
    )


def write_tiny_voice(dirpath, seed: int = 0, **overrides):
    """Materialize a tiny voice on disk (config JSON + npz weights);
    returns the config path.  A ``model=`` override is honored in the
    written config too (not just the in-memory params), so callers can
    materialize larger-than-tiny voices for timing-sensitive checks."""
    import json
    from pathlib import Path

    from sonata_tpu.models.serialization import save_params

    model_dims = dict(overrides.get("model", TINY_MODEL))
    v = tiny_voice(seed=seed, **overrides)
    dirpath = Path(dirpath)
    cfg = {
        "audio": {"sample_rate": 16000, "quality": None},
        "num_speakers": v.config.num_speakers,
        "speaker_id_map": v.config.speaker_id_map,
        "espeak": {"voice": v.config.espeak_voice},
        "num_symbols": v.config.num_symbols,
        "phoneme_id_map": v.config.phoneme_id_map,
        "model": {k: (list(x) if isinstance(x, tuple) else x)
                  for k, x in model_dims.items()},
    }
    cfg["model"]["resblock_dilation_sizes"] = [
        list(d) for d in model_dims["resblock_dilation_sizes"]]
    config_path = dirpath / "voice.onnx.json"
    config_path.write_text(json.dumps(cfg))
    save_params(dirpath / "voice.npz", v.params)
    return config_path


def row_sums(description, attended: int) -> dict:
    """What a row that attends over ``attended`` positions adds to a step
    group's sums, by name, as a unit voice's ``description`` has it (the
    step loop's one look-up a row)."""
    return dict(zip(description.row_sums, description.rows[attended]))


def plan_groups_of_four(monkeypatch) -> None:
    """Let a voice's plan cut a ragged batch of eight into two groups of
    four, so that a test sees the gather and several back programs.  Not
    what ships: on a TPU two programs of four cost more than one of eight
    (``shape_plan.DECODE_MIN_ROWS``)."""
    from sonata_tpu.models import shape_plan

    monkeypatch.setattr(shape_plan, "DECODE_MIN_ROWS", 4)
