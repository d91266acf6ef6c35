#!/usr/bin/env python3
"""The expert products alone, on the chip: ``lax.ragged_dot``, the
megablox ``gmm`` that ships with jax, and this repo's kernel at a list of
tiles, at the shapes the unit voices' programs run.

    python tools/profile_grouped.py [--shapes step|prefill|all]
                                    [--only PREFIX] [--probes] [--out F]

Routes are drawn as the cells' seeded routers draw them (PERF.md §5): a few
experts no row chooses and log-normal weights on the rest, so 61 of 64
experts are touched with the fullest at 4.3 % (``lfm2-24b-a2b``) and 117
of 128 with the fullest at 6.6 % (``sdar-30b-a3b``).  A reading is the
host's clock around one jitted program of ``REPS`` products over different
rows (the launch is a hundredth of it), the least of five; ``GB/s`` counts
the touched experts' weights once.  Every candidate is held to
``ragged_dot`` on the rows of the groups.  ``--probes`` times the kernel
beside two halves of itself: its weights' DMA with the product taken out,
and its products with every visit on one group (the weights fetched once).
Such a reading holds about 0.1 ms a product that is not the kernel's (the
visit list's arithmetic, the outputs' sums): the kernels' own time is in a
cell's capture (PERF.md §5).  A shape whose published width the lanes do
not divide (``nemotron-3-nano-30b-a3b``: 1856 columns laid out in 1920)
states it: ``ragged_dot@published`` and the kernel with whole-dimension
blocks (``own@published``) run on arrays of the published width, the other
candidates on the laid-out ones, all held to the first on the published
columns, and ``GB/s`` counts the published bytes.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

gm = importlib.import_module("sonata_tpu.ops.grouped_matmul")

REPS = 8
#: (name, rows, groups, k, n, valid rows, experts no row chooses, sigma[,
#: (published k, published n)])
STEP_SHAPES = (
    ("lfm2_step.w13", 256, 64, 2048, 3072, 256, 1, 0.3),
    ("lfm2_step.w2", 256, 64, 1536, 2048, 256, 1, 0.3),
    ("sdar_pass.w13", 2048, 128, 2048, 1536, 2048, 10, 0.95),
    ("sdar_pass.w2", 2048, 128, 768, 2048, 2048, 10, 0.95),
    # 256 rows x 6 of which half fall on the 64 held experts
    ("nemotron_step.w13", 1536, 64, 2688, 1920, 768, 0, 0.3, (2688, 1856)),
    ("nemotron_step.w2", 1536, 64, 1920, 2688, 768, 0, 0.3, (1856, 2688)),
    # 256 rows x 8 of which a thirty-second falls on the 8 held experts: the
    # short path's 256 rows, and the 2048 an expert layer not told of its
    # thin share would hand on
    ("pangu_step.w13", 256, 8, 7680, 4096, 64, 0, 0.3),
    ("pangu_step.w2", 256, 8, 2048, 7680, 64, 0, 0.3),
    ("pangu_untold.w13", 2048, 8, 7680, 4096, 64, 0, 0.3),
)
#: a prompt of 68-182 ids at its text bucket (96, 128, 192), the mean
#: prompt's rows valid
PREFILL_SHAPES = (
    ("lfm2_prefill96.w13", 384, 64, 2048, 3072, 328, 1, 0.3),
    ("lfm2_prefill128.w13", 512, 64, 2048, 3072, 448, 1, 0.3),
    ("lfm2_prefill192.w13", 768, 64, 2048, 3072, 640, 1, 0.3),
    ("lfm2_prefill192.w2", 768, 64, 1536, 2048, 640, 1, 0.3),
    ("sdar_prefill96.w13", 768, 128, 2048, 1536, 640, 10, 0.95),
    ("sdar_prefill128.w13", 1024, 128, 2048, 1536, 896, 10, 0.95),
    ("sdar_prefill192.w13", 1536, 128, 2048, 1536, 1280, 10, 0.95),
    ("sdar_prefill192.w2", 1536, 128, 768, 2048, 1280, 10, 0.95),
    ("nemotron_prefill96.w13", 576, 64, 2688, 1920, 246, 0, 0.3,
     (2688, 1856)),
    ("nemotron_prefill128.w13", 768, 64, 2688, 1920, 336, 0, 0.3,
     (2688, 1856)),
    ("nemotron_prefill192.w13", 1152, 64, 2688, 1920, 480, 0, 0.3,
     (2688, 1856)),
    ("nemotron_prefill192.w2", 1152, 64, 1920, 2688, 480, 0, 0.3,
     (1856, 2688)),
)


def draw_sizes(rng, rows: int, groups: int, dead: int, sigma: float):
    p = np.exp(sigma * rng.standard_normal(groups))
    p[rng.permutation(groups)[:dead]] = 0.0
    return rng.multinomial(rows, p / p.sum()).astype(np.int32)


def _stream_only(offsets, group, tile, count, x_ref, w_ref, o_ref):
    """The kernel without its product: what the weights' DMA alone takes."""
    o_ref[...] = w_ref[:o_ref.shape[0], :].astype(o_ref.dtype)


def _one_group(sizes, rows, tm):
    """The visit list with every visit on group 0: the weights are fetched
    once, so what is left is the products and the grid's own cost."""
    offsets, group, tile, count = VISIT_LIST(sizes, rows, tm)
    return offsets, jnp.zeros_like(group), tile, count


VISIT_LIST, KERNEL = gm.visit_list, gm._kernel
#: probes of the kernel at the tiles the rule would pick: patched in while
#: the candidate is traced
PROBES = {"probe_stream": ("_kernel", _stream_only),
          "probe_compute": ("visit_list", _one_group)}


def candidates(k: int, n: int, full: bool, probes: bool) -> list:
    if probes:
        return [("ragged_dot", None)] + [
            (f"{probe}{t}", gm.Tiles(*t)) for t in ((128, n), (32, n))
            for probe in ("own", *PROBES)]
    out = [("ragged_dot", None)]
    mb = [(128, k, 512), (64, k, 512)]
    own = [(tm, tn) for tm in (32, 64, 128) for tn in (512, n)]
    if full:
        mb += [(128, 128, 128), (128, k, 1024), (32, k, 512)]
        own += [(16, 512), (16, n), (32, 1024), (128, 1024)]
    out += [(f"megablox{t}", t) for t in mb]
    out += [(f"own{t}", gm.Tiles(*t)) for t in dict.fromkeys(own)
            if n % t[1] == 0]
    return out


def build(name: str, tiles):
    if name == "ragged_dot":
        def one(x, w, s):
            return lax.ragged_dot(x, w, s, preferred_element_type=jnp.float32)
    elif name.startswith("megablox"):
        mb = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.megablox.gmm")

        def one(x, w, s):
            return mb.gmm(x, w, s, jnp.float32, tiles)
    else:
        probe = PROBES.get(name.split("(")[0])

        def one(x, w, s):
            if probe is None:
                return gm.grouped_matmul_kernel(x, w, s, tiles)
            was = getattr(gm, probe[0])
            setattr(gm, probe[0], probe[1])
            try:
                return gm.grouped_matmul_kernel(x, w, s, tiles)
            finally:
                setattr(gm, probe[0], was)

    @jax.jit
    def many(xs, w, s):
        outs = [one(xs[i], w, s) for i in range(REPS)]
        return sum(jnp.sum(o) for o in outs), outs[0]

    return many


def measure(shape: tuple, full: bool, seed: int,
            probes: bool = False) -> list:
    name, rows, groups, k, n, valid, dead, sigma = shape[:8]
    k_pub, n_pub = shape[8] if len(shape) > 8 else (k, n)
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    xs = jax.random.normal(keys[0], (REPS, rows, k), jnp.bfloat16)
    w = jax.random.normal(keys[1], (groups, k, n), jnp.bfloat16) * 0.02
    cands = candidates(k, n, full, probes)
    rule = gm.tile_rule(rows, groups, k, n, jnp.dtype(jnp.bfloat16))
    if rule is not None and not probes and f"own{tuple(rule)}" not in dict(
            cands):
        cands.append((f"own{tuple(rule)}", rule))
    published = {}
    if (k_pub, n_pub) != (k, n):
        # zero columns and rows outside the published width, and the same
        # numbers as arrays of the published width
        xs = xs * (jnp.arange(k) < k_pub)
        w = w * (jnp.arange(k) < k_pub)[:, None] * (jnp.arange(n) < n_pub)
        published = {"xs": jnp.array(xs[..., :k_pub]),
                     "w": jnp.array(w[:, :k_pub, :n_pub])}
        cands = [("ragged_dot@published", None),
                 ("own@published", gm.Tiles(128, n_pub))] + cands
    draws = [jnp.asarray(draw_sizes(rng, valid, groups, dead, sigma))
             for _ in range(2)]
    touched = float(np.mean([int((np.asarray(s) > 0).sum()) for s in draws]))
    fullest = float(np.mean([int(np.asarray(s).max()) for s in draws])) / valid
    rows_out, ref = [], None
    for cand, tiles in cands:
        line = {"shape": name, "rows": rows, "groups": groups, "k": k,
                "n": n, "valid": valid, "touched": touched,
                "fullest_share": fullest, "candidate": cand}
        if published:
            line["published"] = [k_pub, n_pub]
        at = published if cand.endswith("@published") else {"xs": xs, "w": w}
        try:
            fn = build(cand.split("@")[0], tiles)
            t0 = time.perf_counter()
            _, first = jax.block_until_ready(fn(at["xs"], at["w"], draws[0]))
            line["compile_s"] = time.perf_counter() - t0
            first = first[:valid, :n_pub]
            if ref is None:
                ref = first
                line["ref_abs_max"] = float(jnp.max(jnp.abs(ref)))
            line["err_max"] = float(jnp.max(jnp.abs(first - ref)))
            times = []
            for i in range(6):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(at["xs"], at["w"], draws[i % 2])[0])
                times.append(time.perf_counter() - t0)
            ms = min(times[1:]) * 1e3 / REPS
            line["ms"] = ms
            line["gb_per_s"] = touched * k_pub * n_pub * 2 / ms / 1e6
            line["share_of_819"] = line["gb_per_s"] / 819.0
        except Exception as e:  # a candidate the compiler refuses
            line["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        print(json.dumps(line), flush=True)
        rows_out.append(line)
    return rows_out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="all",
                    choices=("step", "prefill", "all"))
    ap.add_argument("--only", default="",
                    help="shapes whose name starts with this alone")
    ap.add_argument("--probes", action="store_true",
                    help="the kernel beside its two probes only: the "
                         "weights' DMA without the product, the products "
                         "without the DMA")
    ap.add_argument("--seed", type=int, default=3400)
    ap.add_argument("--out", default="chiprun_out/profile_grouped.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 1
    shapes = ()
    if args.shapes in ("step", "all"):
        shapes += tuple((s, True) for s in STEP_SHAPES)
    if args.shapes in ("prefill", "all"):
        shapes += tuple((s, False) for s in PREFILL_SHAPES)
    lines = []
    for shape, full in shapes:
        if shape[0].startswith(args.only):
            lines += measure(shape, full, args.seed, args.probes)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"device": {"platform": device.platform, "kind": device.device_kind},
         "reps": REPS, "lines": lines}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
