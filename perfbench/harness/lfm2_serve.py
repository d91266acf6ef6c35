"""The unit-LM configurations' server command: expands the voice's recipe
on the device, layer by layer, hands the placed weights to the product's
loader and runs the stock server as ``perfbench.harness.serve`` does.

    python perfbench/harness/lfm2_serve.py <voice.json> <server module> \
        <its arguments ...>

10.5 GB of weights do not cross a disk: the writer left a seed
(``recipe.json``), ``lfm2gen.draw`` is the recipe, and the stock ``main``
finds the voice's weights already placed when ``LoadVoice`` asks for the
path (``sonata_tpu.models.unit_voice.place_weights``).  Nothing here
synthesizes.
"""

import json
import os
import sys
import time
from pathlib import Path


def voice_config(voice_path: Path) -> dict:
    """The part of the configuration the recipe reads, rebuilt from what
    the writer left in the voice's directory."""
    voice = json.loads(voice_path.read_text())
    recipe = json.loads((voice_path.parent / "recipe.json").read_text())
    return dict(voice["backbone"], voice=voice,
                weights={"seed": recipe["seed"]})


def place(voice_path: Path) -> None:
    import jax

    from perfbench.harness import lfm2gen
    from sonata_tpu.models import lfm2, unit_voice
    from sonata_tpu.models.serialization import load_params
    from sonata_tpu.utils.jax_cache import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    config = voice_config(voice_path)
    t0 = time.monotonic()
    layers = []
    for i in range(int(config["num_hidden_layers"])):
        layers.append(lfm2.pack_layer(lfm2gen.draw_layer(config, i)))
        jax.block_until_ready(layers[-1])
    weights = {
        "backbone": {"embed": lfm2gen.draw(config, "embed"),
                     "norm_f": lfm2gen.draw(config, "norm_f").astype(
                         "float32"),
                     "layers": layers},
        "unit_table": lfm2gen.draw(config, "unit_table"),
        "generator": jax.device_put(load_params(
            voice_path.parent / "generator.npz"))}
    jax.block_until_ready(weights)
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(weights))
    print(f"lfm2_serve: {held / 1e9:.3f} GB of weights placed in "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    unit_voice.place_weights(voice_path, weights)


def main(argv: list) -> int:
    sys.path.insert(0, os.getcwd())     # spawned from the root of a checkout
    from perfbench.harness import serve

    place(Path(argv[1]))
    return serve.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
