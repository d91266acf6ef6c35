"""The comparison that decides ``correct`` for a unit voice whose backbone
generates by diffusion over blocks (``sdar_moe``) behind the stock RPCs:
``compare(job, config)`` (the protocol: ``reference/check.py``).

The timed path leaves, for every replayed row (``SONATA_AR_DUMP_DIR`` under
``work_dir``, request ids ``pb-check-*``), what it produced through
prefill and then passes through the cache: the row's tokens as committed
(prompt, units, the last block's surplus), the experts every position chose
in its commit pass, the pass at which every position was unmasked, and, for
every pass (denoising and commit) of the row's first block, its last and
every sixteenth between, the block as it went in (mask tokens and all), its
float32 logits ``[B, V]`` and the experts it chose.

For each such pass the plain reference (``sdar_ref.py``: float32
``highest``, no cache) is owed **the row's clean committed prefix + the
block as the pass saw it, run whole**; its logits at the block's positions
are held against the served ones.  That holds the cache to the commit pass
(a prefix kept from a denoising pass would differ), the mask to ``M``, and
prefill + passes through the cache to the whole forward pass.  *In blocks
that fit*: one reference pass a row runs the row's committed tokens whole
under ``M`` (a commit pass saw exactly that, so its logits are read there)
and, beside them in the same sequence, every kept denoising pass's block as
extra positions that carry the block's position ids and see the committed
positions before the block and themselves, and that nothing else sees
(``pack``).  Each of those rows of the attention is the row it would be in a
pass over prefix + block alone, and every other operation works a position
at a time, so nothing is approximated; ``tests/perfbench/test_sdar_cell.py``
holds the packing to the passes run one by one.  The reference runs twice.

**On its own routes**: ``route_flip_share`` (the share of the served routing
decisions, every committed position and every kept pass, whose expert the
reference did not choose) and ``logit_err_median`` / ``logit_err_p99`` (over
all kept positions, the r.m.s. difference of the served logits from the
reference's over the vocabulary, relative to the spread of the reference's
logits at that position; what a flip does to later layers and positions
stays in these, so they are wide).

**On the served routes** (``forced``: every expert layer computes the
experts the served path chose, weighted by the reference's own scores of
them): ``logit_err_forced_median`` / ``logit_err_forced_p99`` (what
bfloat16 inputs, the cache and the grouped products cost),
``route_flip_forced_share`` (the reference's own choice at the served
path's hidden states against the served one), and ``unmask_regret_max``:
at every kept denoising pass, in spreads of the logits at the position,
(a) for every position the served path unmasked, this pass's largest logit
among the ids a row may choose minus its logit of the id that was chosen,
and (b) the confidence (the best id's log-probability over those ids) of
the surest position the served path left masked minus that of the least
sure one it unmasked, where that is positive.  The replay is greedy: an id
or a position the reference ranks below its best by more than the two
differ in their logits is a wrong choice.

And whatever the routes: ``rows_length_off`` (rows whose text stage, length
rule ``round(frames_per_id * ids)``, launches or dump disagree with the
reference's: a token row of another length, a mask token left in it, a
prompt that is not the sentence's, a kept block that is not what the
record of unmasking says the pass saw, a pass that unmasked another number
of positions than the schedule's) and ``audio_err_max`` /
``audio_err_ratio_median`` (``lfm2_check.audio_numbers``: the served samples
against the reference generator over the row's ``budget`` units).

Controls (``PERFBENCH_CONTROL``; each has to come out as not correct).
``reference_fp8`` puts, in the program's place, the reference one notch
below the stated precision (weights float8 e4m3, what the configuration
states as float32 rounded to bfloat16; its own choices of ids and
positions).  ``no_commit`` puts there the reference over a cache that took
every block's keys and values from its *last denoising pass*: the
committed prefix every kept pass sees is the blocks as those passes saw
them, mask tokens and all.  ``causal_block`` leaves the served path as it is
and makes the reference mask inside a block causally.
``PERFBENCH_ALSO_CONTROLS=<names>`` leaves the run as it is and adds the
controls' numbers under ``info.controls``.  ``info.numbers`` holds every
number of the run, whatever the limits file names.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import numpy as np

from perfbench.harness import parts, textgen
from perfbench.reference import lfm2_check

#: a control's storage types, as ``lfm2_check.CONTROLS`` has them
LOW = lfm2_check.CONTROLS["reference_fp8"]
CONTROLS = ("reference_fp8", "no_commit", "causal_block")


def schedule(config: dict) -> tuple:
    """``(block length, denoising passes a block, mask id)`` of the voice."""
    units = config["voice"]["units"]
    return (int(units["block_length"]), int(units["denoising_steps"]),
            int(units["mask_id"]))


def load_rows(job: dict, config: dict, root: Path) -> list:
    """One row per replayed sentence: the reference's own ids, the served
    samples and the dump the timed path left (its arrays read when asked
    for: a row's logits are tens of megabytes)."""
    lexicon = textgen.Lexicon(root / job["words"])
    id_map = config["voice"]["phoneme_id_map"]
    audio = np.load(job["sampled_audio"])
    dump_dir = Path(job["work_dir"]) / "ar_dump"
    rows = []
    for req in job["sampled"]:
        if not req["ok"]:
            continue
        dumps = [np.load(p) for p in sorted(
            dump_dir.glob(f"{req['rid']}.*.npz"))]
        for i, sentence in enumerate(req["sentences"]):
            ids = textgen.text_to_ids(lexicon, sentence, id_map)
            mine = [d for d in dumps if d["ids"].tolist() == ids]
            rows.append({"ids": ids, "pcm": audio[f"{req['seq']}_{i}"],
                         "dump": mine[0] if mine else None})
    return rows


def seen_at(tokens, unmasked_at, start: int, block: int, pass_no: int,
            mask_id: int):
    """The block at ``start`` as pass ``pass_no`` saw it, by the record: a
    position unmasked at that pass or later still held the mask token."""
    at = slice(start, start + block)
    return np.where(unmasked_at[at] >= pass_no, mask_id, tokens[at])


def sound_row(r: dict, config: dict, hop: int) -> bool:
    """Whether the row's dump is what the length rule, the schedule and its
    own record say it should be; fills in what the comparison reads."""
    fpi = float(config["voice"]["units"]["frames_per_id"])
    b, steps, mask = schedule(config)
    d, n = r["dump"], len(r["ids"])
    want = max(1, round(fpi * n))
    if d is None or len(r["pcm"]) != want * hop:
        return False
    tokens, unmasked_at = d["tokens"], d["unmasked_at"]
    first = n // b * b
    blocks = -(-(n % b + want) // b)
    if len(tokens) != first + blocks * b or int(d["block_length"]) != b \
            or int(d["denoising_steps"]) != steps \
            or tokens[:n].tolist() != r["ids"] or (tokens == mask).any() \
            or (unmasked_at[:n] != -1).any() \
            or (unmasked_at[n:] < 0).any() or (unmasked_at >= steps).any():
        return False
    base, more = divmod(b, steps)
    passes = []
    for m, launch in enumerate(d["passes"].tolist()):
        start, pass_no = first + launch // (steps + 1) * b, \
            launch % (steps + 1)
        seen = seen_at(tokens, unmasked_at, start, b, pass_no, mask)
        if not np.array_equal(seen, d["seen"][m]):
            return False
        taken = unmasked_at[start:start + b] == pass_no
        if pass_no < steps and taken.sum() != min(
                base + (pass_no < more), (seen == mask).sum()):
            return False
        passes.append({"start": start, "pass": pass_no, "seen": seen,
                       "taken": taken})
    r.update(tokens=tokens, unmasked_at=unmasked_at, passes=passes,
             units=tokens[n:n + want], routes=d["routes"].astype(np.int64),
             pass_routes=d["pass_routes"].astype(np.int64))
    return len(passes) > 0


def pack(r: dict, config: dict, stand_in: bool = False,
         causal: bool = False) -> dict:
    """One sequence for the reference: the row's committed tokens, then the
    kept passes' blocks as extra positions, with the positions' ids and who
    sees whom (``visible``: whole inside a block, or causal).  ``at`` lists,
    for every kept pass in order, where its block's logits are read.
    ``stand_in``: the sequence a cache without commits amounts to: every
    generated block stands as its last denoising pass saw it, and the commit
    passes' clean blocks are extra positions too."""
    b, steps, mask = schedule(config)
    base = r["tokens"].copy()
    if stand_in:
        base = np.where(r["unmasked_at"] >= steps - 1, mask, base)
    t = len(base)
    extra = [p for p in r["passes"] if stand_in or p["pass"] < steps]
    size = t + b * len(extra)
    tokens = np.zeros((size,), np.int32)
    tokens[:t] = base
    positions = np.zeros((size,), np.int32)
    positions[:t] = np.arange(t)
    visible = np.zeros((size, size), bool)
    block_of = np.arange(t) if causal else np.arange(t) // b
    visible[:t, :t] = block_of[:, None] >= block_of[None, :]
    inside = np.tril(np.ones((b, b), bool)) if causal else True
    at, where = [], {}
    for k, p in enumerate(extra):
        a = t + k * b
        tokens[a:a + b] = p["seen"]
        positions[a:a + b] = p["start"] + np.arange(b)
        visible[a:a + b, :p["start"]] = True
        visible[a:a + b, a:a + b] = inside
        where[id(p)] = a
    for p in r["passes"]:
        a = where.get(id(p), p["start"])
        at += list(range(a, a + b))
    return {"tokens": tokens, "positions": positions, "visible": visible,
            "at": np.asarray(at, np.int32)}


def padded(packs: list, t_pad: int) -> list:
    out = []
    for p in packs:
        n = len(p["tokens"])
        tokens = np.zeros((t_pad,), np.int32)
        tokens[:n] = p["tokens"]
        positions = np.zeros((t_pad,), np.int32)
        positions[:n] = p["positions"]
        # a padding position sees itself and is seen by nothing
        visible = np.eye(t_pad, dtype=bool)
        visible[:n, :n] = p["visible"]
        out.append((tokens, positions, visible))
    return out


def reference_pass(ref, writer, config: dict, packs: list, control=None,
                   forced=None) -> dict:
    """The reference over every row's packed sequence: layers outside, rows
    inside, one layer's weights on the device at a time.  ``forced``: per
    row the experts ``[positions, layers, k]`` its expert layers compute
    instead of their own choice.  Returns per row the final hidden states
    and the experts the reference chose, and the head's weights."""
    import jax
    import jax.numpy as jnp

    weights_dtype, act_dtype = control[:2] if control else (None, None)

    def weights(tree):
        def one(a):
            if weights_dtype is not None:
                a = a.astype(getattr(jnp, weights_dtype))
            return a.astype(jnp.float32)
        return jax.tree_util.tree_map(one, tree)

    round_to = None
    if act_dtype is not None:
        def round_to(a):
            return a.astype(getattr(jnp, act_dtype)).astype(jnp.float32)

    bb = writer.backbone(config)
    t_pad = lfm2_check._round_up(max(len(p["tokens"]) for p in packs), 128)
    rows = padded(packs, t_pad)
    embed = weights(writer.draw(config, "embed"))
    hidden = [embed[jnp.asarray(tokens)] for tokens, _, _ in rows]
    del embed
    if forced is not None:
        walked = []
        for f in forced:
            wide = np.zeros((t_pad,) + f.shape[1:], np.int32)
            wide[:len(f)] = f
            walked.append(wide)
    routes = [[] for _ in rows]
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda h, p, visible, positions, f=None: ref.layer(
            h, p, bb, visible, positions, None, round_to, f))
        for i in range(int(bb["num_hidden_layers"])):
            p = weights(writer.draw_layer(config, i))
            for k, (_, positions, visible) in enumerate(rows):
                args = (walked[k][:, i],) if forced is not None else ()
                hidden[k], chosen = step(hidden[k], p, visible, positions,
                                         *args)
                routes[k].append(np.asarray(chosen))
            del p
    return {"hidden": hidden,
            "routes": [np.stack(r, 1)[:len(p["tokens"])]
                       for r, p in zip(routes, packs)],
            "head": weights(writer.draw(config, "head")),
            "norm_f": weights(writer.draw(config, "norm_f"))}


def readers(ref, config: dict, bb: dict):
    """Two jitted functions, the head's weights as arguments (captured,
    they would be compiled in as a constant of a gigabyte): a row's logits
    at the kept positions, and their reduction against another set of
    logits and of chosen ids."""
    import jax
    import jax.numpy as jnp

    units = config["voice"]["units"]
    first, stop, mask = (int(units["first_id"]), int(units["stop_id"]),
                         int(units["mask_id"]))

    @jax.jit
    def logits_at(h, at, head, norm_f):
        with jax.default_matmul_precision("highest"):
            return ref.head(h[at], head, norm_f, bb)

    @jax.jit
    def reduce(logits, served, chosen):
        ids = jnp.arange(logits.shape[-1])
        allowed = jnp.where((ids >= first) & (ids != stop) & (ids != mask),
                            logits, -jnp.inf)
        best = jnp.max(allowed, -1)
        took = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
        return {"err": jnp.sqrt(jnp.mean((served - logits) ** 2, -1))
                / jnp.std(logits, -1),
                "spread": jnp.std(logits, -1), "best": best,
                "best_id": jnp.argmax(allowed, -1), "took": took,
                "confidence": best - jax.nn.logsumexp(allowed, -1)}

    return logits_at, reduce


def choices(r: dict, config: dict, small=None) -> list:
    """Per kept pass, the positions unmasked and the ids put there: the
    served path's (by its record) or, from a stand-in's reduced logits,
    what a program with those logits would have chosen."""
    b, steps, mask = schedule(config)
    base, more = divmod(b, steps)
    out = []
    for m, p in enumerate(r["passes"]):
        ids = r["tokens"][p["start"]:p["start"] + b]
        taken = p["taken"]
        if small is not None and p["pass"] < steps:
            masked = p["seen"] == mask
            sure = np.where(masked, small["confidence"][m * b:(m + 1) * b],
                            -np.inf)
            first = np.argsort(-sure, kind="stable")[:base + (
                p["pass"] < more)]
            taken = np.zeros((b,), bool)
            taken[first[masked[first]]] = True
            ids = small["best_id"][m * b:(m + 1) * b]
        out.append((taken, np.asarray(ids, np.int32)))
    return out


def unmask_regrets(r: dict, config: dict, small: dict, chose: list) -> list:
    """Per kept denoising pass, the larger of the two regrets of the
    module's docstring (0 where the pass unmasked nothing)."""
    b, steps, mask = schedule(config)
    out = []
    for m, p in enumerate(r["passes"]):
        taken = chose[m][0]
        if p["pass"] >= steps or not taken.any():
            continue
        s = slice(m * b, (m + 1) * b)
        spread, sure = small["spread"][s], small["confidence"][s]
        regret = np.max(((small["best"][s] - small["took"][s])
                         / spread)[taken])
        left = (p["seen"] == mask) & ~taken
        if left.any():
            least = np.flatnonzero(taken)[np.argmin(sure[taken])]
            regret = max(regret, (sure[left].max() - sure[least])
                         / spread[least])
        out.append(float(regret))
    return out


def compare(job: dict, config: dict) -> dict:
    import jax.numpy as jnp

    root = Path(job["root"])
    ref = parts.load(root, job["paths"], config, "reference")
    writer = parts.load(root, job["paths"], config, "writer")
    t0 = time.monotonic()
    rows = load_rows(job, config, root)
    hop = writer.describe(config)["samples_per_frame"]
    sound = [r for r in rows if sound_row(r, config, hop)]
    numbers = {"rows_length_off": len(rows) - len(sound)}
    info = {"rows": len(rows), "rows_compared": len(sound)}
    if not sound:
        return {"numbers": numbers, "info": info}
    logits_at, reduce = readers(ref, config, writer.backbone(config))
    b, steps, _ = schedule(config)
    wide = lfm2_check._round_up(max(len(r["passes"]) for r in sound) * b, 16)

    def at_of(p: dict):
        at = np.full((wide,), p["at"][-1], np.int32)
        at[:len(p["at"])] = p["at"]
        return at

    def served_logits(r: dict):
        flat = r["dump"]["logits"].reshape(-1, r["dump"]["logits"].shape[-1])
        out = np.zeros((wide, flat.shape[1]), np.float32)
        out[:len(flat)] = flat
        return out

    def packed_routes(r: dict, p: dict) -> np.ndarray:
        """The served routes in the packed sequence's order: the commit
        passes' at the committed positions, the kept denoising passes' at
        the extra ones."""
        out = np.zeros((len(p["tokens"]),) + r["routes"].shape[1:], np.int64)
        out[:len(r["routes"])] = r["routes"]
        extra = [r["pass_routes"][m] for m, q in enumerate(r["passes"])
                 if q["pass"] < steps]
        if extra:
            out[len(r["routes"]):] = np.concatenate(extra)
        return out

    cache: dict = {}

    def sound_pass(causal: bool) -> tuple:
        if causal not in cache:
            packs = [pack(r, config, causal=causal) for r in sound]
            cache[causal] = packs, reference_pass(ref, writer, config, packs)
        return cache[causal]

    def judge(control) -> tuple:
        """The numbers the limits name, then the others."""
        packs, own = sound_pass(control == "causal_block")
        routes = [packed_routes(r, p) for r, p in zip(sound, packs)]
        stand = stand_packs = None
        if control in ("reference_fp8", "no_commit"):
            low = control == "reference_fp8"
            stand_packs = packs if low else [
                pack(r, config, stand_in=True) for r in sound]
            stand = reference_pass(ref, writer, config, stand_packs,
                                   LOW if low else None)
            if low:
                routes = stand["routes"]
        walked = reference_pass(ref, writer, config, packs, None, routes)
        errs, forced_errs, regrets, own_regrets = [], [], [], []
        for k, (r, p) in enumerate(zip(sound, packs)):
            at, n_at = at_of(p), len(p["at"])
            mine = logits_at(own["hidden"][k], at, own["head"],
                             own["norm_f"])
            theirs = logits_at(walked["hidden"][k], at, own["head"],
                               own["norm_f"])
            if stand is None:
                served, chose = jnp.asarray(served_logits(r)), choices(
                    r, config)
            else:
                served = logits_at(stand["hidden"][k], at_of(stand_packs[k]),
                                   stand["head"], stand["norm_f"])
                small = {key: np.asarray(v) for key, v in reduce(
                    served, served, jnp.zeros((wide,), jnp.int32)).items()}
                chose = choices(r, config, small)
            chosen = np.zeros((wide,), np.int32)
            chosen[:n_at] = np.concatenate([ids for _, ids in chose])
            for logits, err_list, regret_list in (
                    (mine, errs, own_regrets),
                    (theirs, forced_errs, regrets)):
                small = {key: np.asarray(v)[:n_at] for key, v in reduce(
                    logits, served, jnp.asarray(chosen)).items()}
                err_list.append(small["err"])
                regret_list += unmask_regrets(r, config, small, chose)
        errs, forced_errs = np.concatenate(errs), np.concatenate(forced_errs)
        compared = {
            "logit_err_median": float(np.median(errs)),
            "logit_err_p99": float(np.quantile(errs, 0.99)),
            "route_flip_share": lfm2_check.flip_share(routes, own["routes"]),
            "logit_err_forced_median": float(np.median(forced_errs)),
            "logit_err_forced_p99": float(np.quantile(forced_errs, 0.99)),
            "route_flip_forced_share": lfm2_check.flip_share(
                routes, walked["routes"]),
            "unmask_regret_max": float(max(regrets))}
        more = {"logit_err_max": float(errs.max()),
                "logit_err_forced_max": float(forced_errs.max()),
                "unmask_regret_p99": float(np.quantile(regrets, 0.99)),
                "unmask_regret_own_max": float(max(own_regrets)),
                "positions_compared": int(len(errs)),
                "passes_compared": int(len(regrets))}
        return compared, more

    control = os.environ.get("PERFBENCH_CONTROL")
    if control and control not in CONTROLS:
        raise ValueError(f"no control {control!r} (known: {CONTROLS})")
    compared, more = judge(control)
    if control:
        info["control"] = control
    numbers.update(compared)
    info.update(more)
    also = {name: judge(name) for name in os.environ.get(
        "PERFBENCH_ALSO_CONTROLS", "").split(",") if name}
    cache.clear()
    low = LOW if control == "reference_fp8" else None
    audio, audio_info = lfm2_check.audio_numbers(config, writer, sound, root,
                                                 low)
    numbers.update(audio)
    info.update(audio_info)
    for name, (logit, logit_more) in also.items():
        info.setdefault("controls", {})[name] = dict(
            logit, **logit_more, **(lfm2_check.audio_numbers(
                config, writer, sound, root, LOW)[0]
                if name == "reference_fp8" else audio))
    info["numbers"] = dict(numbers)
    info.update({
        "frames_compared": int(sum(len(r["units"]) for r in sound)),
        "longest_row_frames": int(max(len(r["units"]) for r in sound)),
        "longest_row_positions": int(max(len(r["tokens"]) for r in sound)),
        "kept_passes_a_row": float(np.mean([len(r["passes"])
                                            for r in sound])),
        "reference_s": time.monotonic() - t0})
    return {"numbers": numbers, "info": info}
