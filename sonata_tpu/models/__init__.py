"""Model implementations (analogue of ``crates/sonata/models``)."""

import json
from pathlib import Path
from typing import Union

from ..core import FailedToLoadResource
from .config import (
    ModelConfig,
    SynthesisConfig,
    VitsHyperParams,
    default_phoneme_id_map,
)
from .piper import PiperVoice


def voice_family(config_path: Union[str, Path]) -> str:
    """The voice JSON's ``family`` key; a JSON without one (every Piper
    voice, and a file that cannot be read: the Piper loader reports it) is
    ``piper``."""
    try:
        data = json.loads(Path(config_path).read_text(encoding="utf-8"))
    except (OSError, ValueError):
        return "piper"
    return str(data.get("family", "piper")) if isinstance(data, dict) \
        else "piper"


def from_config_path(config_path: Union[str, Path], **kwargs):
    """Load a voice from its JSON config (reference factory:
    ``crates/sonata/models/piper/src/lib.rs:88-110``).  The JSON's
    ``family`` key picks the implementation of :class:`~sonata_tpu.core.Model`:
    absent or ``piper`` a :class:`PiperVoice`, ``unit_lm`` a
    :class:`~sonata_tpu.models.unit_voice.UnitVoice`."""
    family = voice_family(config_path)
    if family == "piper":
        return PiperVoice.from_config_path(config_path, **kwargs)
    if family == "unit_lm":
        from .unit_voice import UnitVoice

        return UnitVoice.from_config_path(config_path, **kwargs)
    raise FailedToLoadResource(
        f"{config_path}: no voice family {family!r} (piper, unit_lm)")


__all__ = [
    "ModelConfig",
    "SynthesisConfig",
    "VitsHyperParams",
    "default_phoneme_id_map",
    "PiperVoice",
    "from_config_path",
]
