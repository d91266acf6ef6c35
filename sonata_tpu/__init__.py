"""sonata-tpu: a TPU-native neural text-to-speech serving framework.

Capability-parity rebuild of mush42/sonata (see SURVEY.md) designed
TPU-first: the VITS compute path is JAX/XLA (jit/pjit over a device
mesh), the runtime around it is Python + C++ (phonemizer
shim, prosody DSP, C ABI), and the frontends (CLI, gRPC, Python, C) mirror
the reference's surface.
"""

__version__ = "0.1.0"

# Sharding-invariant PRNG semantics, set before any trace can run: with
# the legacy non-partitionable threefry, a random draw INSIDE a sharded
# jit can produce different values than the identical unsharded program
# (observed on jax 0.4.37: duration/decoder noise diverging between a
# meshed and a plain dispatch of the same batch).  Partitionable threefry
# defines draw values independently of how XLA partitions the
# computation, which — together with the per-row keys in
# ``models.vits.per_row_normal`` — is what makes sharded-vs-unsharded
# synthesis bit-stable and a request's audio independent of its batch
# neighbors.  Must happen at import, not first mesh use: flipping the
# flag mid-process would split the executable caches across two RNG
# semantics.
import jax as _jax

_jax.config.update("jax_threefry_partitionable", True)
del _jax

from .core import (
    AudioInfo,
    BaseModel,
    FailedToLoadResource,
    Model,
    OperationError,
    Phonemes,
    PhonemizationError,
    SonataError,
)
from .audio import Audio, AudioSamples

__all__ = [
    "__version__",
    "AudioInfo",
    "BaseModel",
    "FailedToLoadResource",
    "Model",
    "OperationError",
    "Phonemes",
    "PhonemizationError",
    "SonataError",
    "Audio",
    "AudioSamples",
]
