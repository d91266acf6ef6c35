"""The GigaChat-3.5 unit voices' server command: ``lfm2_serve.py``'s
way with ``gigachatgen``'s recipe.  Expands the voice's recipe on the device,
layer by layer, hands the placed weights to the product's loader and runs
the stock server as ``perfbench.harness.serve`` does.

    python perfbench/harness/gigachat_serve.py <voice.json> \
        <server module> <its arguments ...>

6.6 GB of weights do not cross a disk: the writer left a seed
(``recipe.json``), ``gigachatgen.draw`` is the recipe, and the stock ``main``
finds the voice's weights already placed when ``LoadVoice`` asks for the
path (``sonata_tpu.models.unit_voice.place_weights``).  Nothing here
synthesizes.
"""

import os
import sys
import time
from pathlib import Path


def place(voice_path: Path) -> None:
    import jax

    from perfbench.harness import gigachatgen
    from perfbench.harness.lfm2_serve import voice_config
    from sonata_tpu.models import unit_voice
    from sonata_tpu.models.serialization import load_params
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    config = voice_config(voice_path)
    built = unit_voice.make_backbone(gigachatgen.backbone(config),
                                     config["voice"]["units"])
    t0 = time.monotonic()
    layers = []
    for i in range(built.layers):
        layers.append(built.pack_layer(gigachatgen.draw_layer(config, i)))
        jax.block_until_ready(layers[-1])
    weights = {
        "backbone": {"embed": gigachatgen.draw(config, "embed"),
                     "head": gigachatgen.draw(config, "head"),
                     "norm_f": gigachatgen.draw(config, "norm_f").astype(
                         "float32"),
                     "layers": layers},
        "unit_table": gigachatgen.draw(config, "unit_table"),
        "generator": jax.device_put(load_params(
            voice_path.parent / "generator.npz"))}
    jax.block_until_ready(weights)
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(weights))
    print(f"gigachat_serve: {held / 1e9:.3f} GB of weights placed in "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    unit_voice.place_weights(voice_path, weights)


def main(argv: list) -> int:
    sys.path.insert(0, os.getcwd())     # spawned from the root of a checkout
    from perfbench.harness import serve

    place(Path(argv[1]))
    return serve.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
