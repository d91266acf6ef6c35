"""The comparison that decides ``correct`` for a unit-LM voice behind the
stock RPCs: ``compare(job, config)`` (the protocol: ``reference/check.py``).

The timed path leaves, for every replayed row (``SONATA_AR_DUMP_DIR`` under
``work_dir``, request ids ``pb-check-*``), what it produced through
prefill and then decode steps through the cache: the prompt's ids, every
unit chosen, the experts every token chose in every expert layer, and the
float32 logits over the whole vocabulary behind its first unit, its last
and every 32nd between.  Here the plain reference (``lfm2_ref.py``: float32
``highest``, no cache) runs **one full forward pass** over each row's
prompt + chosen units (teacher-forced), one layer's weights on the device
at a time, and is held against that.  It runs twice.

**On its own routes**, which says whether the served path routes as the
reference does, and how far a row drifts where it does not:

- ``route_flip_share``: the share of routing decisions (position, expert
  layer, slot of the top-k) whose expert the reference did not choose.
  Near-ties flip by rounding; they are counted and limited, never dropped;
- ``logit_err_median`` / ``logit_err_p99``: over all dumped positions, the
  r.m.s. difference of the served logits from the reference's over the
  vocabulary, relative to the spread (standard deviation over the
  vocabulary) of the reference's logits at that position.  What a flip
  does to later layers and positions stays in these numbers, so they are
  wide: with seeded routers a flipped expert moves a token's hidden state
  by a tenth, and nearly every token meets a flip.

**On the served routes** (``forced``: every expert layer computes the
experts the served path chose, weighted by the reference's own scores of
them), which holds the served arithmetic, cache and state to the
reference's without a near-tie between them:

- ``logit_err_forced_median`` / ``logit_err_forced_p99``: as above, against
  this pass: what bfloat16 inputs, the cache and the grouped products cost;
- ``greedy_regret_max``: at **every** step of every row, this pass's
  largest logit among the ids a row may choose minus its logit of the id
  that was chosen, relative to the same spread (the replay is greedy: a
  served choice the reference ranks below its best by more than the two
  differ in their logits is a wrong token);
- ``route_flip_forced_share``: the reference's own choice at the served
  path's hidden states against the served one: what is left of the flips
  once their cascade is taken out.

And whatever the routes:

- ``rows_length_off``: rows whose text stage, length rule (``round(
  frames_per_id * ids)`` frames) or dump disagree with the reference's;
- ``audio_err_max`` / ``audio_err_ratio_median``: the served samples
  against the reference generator (``vits_ref.generator``) over the chosen
  units' latents, as ``vits_check.py`` measures them.

Controls (``PERFBENCH_CONTROL``; each has to come out as not correct).
``reference_fp8`` puts, in the program's place, the reference one notch
below the stated precision: weights rounded to float8 (e4m3), and what the
configuration states as float32 (the residual stream, router scores, the
softmax) rounded to bfloat16.  ``wrong_unit`` plants the fault
``greedy_regret_max`` is named for: one step of one row (the first row's
last, which is fed back to nothing) is read as having chosen the next unit
id instead of its own; everything else is as served.
``PERFBENCH_ALSO_CONTROLS=<names>`` leaves the run as it is and adds the
controls' numbers under ``info.controls``: how the limits' upper ends are
read beside a sound run's.  ``info.numbers`` holds every number of the
run, whatever the limits file names.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import numpy as np

from perfbench.harness import parts, textgen

VITS_REF = "perfbench/reference/vits_ref.py"
#: a control's storage types: the backbone's weights, what the
#: configuration states as float32 there, and the generator (weights and
#: every convolution's input and output)
CONTROLS = {"reference_fp8": ("float8_e4m3fn", "bfloat16", "bfloat16")}
#: the control that alters one chosen unit and no precision
WRONG_UNIT = "wrong_unit"
#: positions a row's logits are read at, at most (a slot of 1024 positions
#: dumps every 32nd unit, the first and the last)
DUMPED = 40


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def load_rows(job: dict, config: dict, root: Path) -> list:
    """One row per replayed sentence: the reference's own ids, the served
    samples and the dump the timed path left."""
    lexicon = textgen.Lexicon(root / job["words"])
    id_map = config["voice"]["phoneme_id_map"]
    audio = np.load(job["sampled_audio"])
    dump_dir = Path(job["work_dir"]) / "ar_dump"
    rows = []
    for req in job["sampled"]:
        if not req["ok"]:
            continue
        dumps = [dict(np.load(p)) for p in sorted(
            dump_dir.glob(f"{req['rid']}.*.npz"))]
        for i, sentence in enumerate(req["sentences"]):
            ids = textgen.text_to_ids(lexicon, sentence, id_map)
            mine = [d for d in dumps if d["ids"].tolist() == ids]
            rows.append({"ids": ids, "pcm": audio[f"{req['seq']}_{i}"],
                         "dump": mine[0] if mine else None})
    return rows


def reference_pass(ref, writer, config: dict, rows: list, t_pad: int,
                   control=None, forced=None):
    """The reference over every row (padded to ``t_pad``: every operator is
    causal, so what follows a row's end does not reach it): layers outside,
    rows inside, one layer's weights on the device at a time.  ``forced``:
    per row the experts ``[tokens, expert layers, k]`` its expert layers
    compute instead of their own choice.  Returns per row the final hidden
    states and the experts the reference chose."""
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
    embed = weights(writer.draw(config, "embed"))
    tokens = np.zeros((len(rows), t_pad), np.int32)
    for k, r in enumerate(rows):
        tokens[k, :len(r["tokens"])] = r["tokens"]
    hidden = [embed[jnp.asarray(t)] for t in tokens]
    if forced is not None:
        walked = []
        for f in forced:
            padded = np.zeros((t_pad,) + f.shape[1:], np.int32)
            padded[:len(f)] = f
            walked.append(padded)
    routes = [[] for _ in rows]
    fns: dict = {}
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(bb["layer_types"]):
            dense = i < int(bb["num_dense_layers"])
            walk = forced is not None and not dense
            if (kind, walk, dense) not in fns:
                fns[kind, walk, dense] = jax.jit(
                    lambda h, p, f=None, kind=kind, dense=dense: ref.layer(
                        h, p, kind, dense, bb, None, round_to, f))
            p = weights(writer.draw_layer(config, i))
            for k in range(len(rows)):
                args = (walked[k][:, len(routes[k])],) if walk else ()
                hidden[k], chosen = fns[kind, walk, dense](hidden[k], p,
                                                           *args)
                if chosen is not None:
                    routes[k].append(np.asarray(chosen))
            del p
    norm_f = weights(writer.draw(config, "norm_f"))
    return hidden, [np.stack(r, 1) for r in routes], embed, norm_f


def reducer(ref, config: dict):
    """One jitted reduction of a row's hidden states, for every pass: per
    step the regret, and at ``at`` the spread and the logits."""
    import jax
    import jax.numpy as jnp

    units = config["voice"]["units"]
    first, stop = int(units["first_id"]), int(units["stop_id"])

    # the embedding goes in as an argument (captured, it would be compiled
    # into the program as a constant of half a gigabyte), and every row is
    # read at DUMPED positions (the last repeated), so one program serves all
    @jax.jit
    def reduce(h, chosen, at, embed, norm_f):
        with jax.default_matmul_precision("highest"):
            logits = ref.head(h, embed, norm_f, config)
        ids = jnp.arange(logits.shape[-1])
        allowed = (ids >= first) & (ids != stop)
        spread = jnp.std(logits, -1)
        best = jnp.max(jnp.where(allowed, logits, -jnp.inf), -1)
        took = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
        return (best - took) / spread, spread[at], logits[at]

    return reduce


def logit_numbers(reduce, rows: list, hidden, embed, norm_f, served: list,
                  units: list) -> dict:
    """Per dumped position the relative error of the ``served`` logits, per
    step the regret of the ``units`` chosen."""
    errs, regrets = [], []
    for k, r in enumerate(rows):
        n, count = len(r["ids"]), len(units[k])
        t_pad = hidden[k].shape[0]
        chosen = np.zeros((t_pad,), np.int32)
        chosen[n - 1:n - 1 + count] = units[k]
        m = len(r["logit_units"])
        at = np.full((DUMPED,), n - 1 + int(r["logit_units"][-1]), np.int32)
        at[:m] = n - 1 + np.asarray(r["logit_units"])
        regret, spread, logits = reduce(hidden[k], chosen, at, embed,
                                        norm_f)
        spread, logits = np.asarray(spread)[:m], np.asarray(logits)[:m]
        regrets.append(np.asarray(regret)[n - 1:n - 1 + count])
        rms = np.sqrt(np.mean((served[k] - logits) ** 2, -1))
        errs.append(rms / spread)
    errs, regrets = np.concatenate(errs), np.concatenate(regrets)
    return {"logit_err_median": float(np.median(errs)),
            "logit_err_p99": float(np.quantile(errs, 0.99)),
            "logit_err_max": float(errs.max()),
            "greedy_regret_max": float(regrets.max()),
            "greedy_regret_p99": float(np.quantile(regrets, 0.99)),
            "positions_compared": int(len(errs)),
            "steps_compared": int(len(regrets))}


def flip_share(served: list, reference: list) -> float:
    """Share of the served routing decisions the reference did not make."""
    flips = total = 0
    for s, r in zip(served, reference):
        r = r[:len(s)]
        flips += int(np.sum(~np.any(
            s[..., :, None] == r[..., None, :], axis=-1)))
        total += s.size
    return flips / max(total, 1)


def low_generator(vits, dtype: str):
    """``vits_ref.generator`` with its weights and every convolution's
    input and output rounded to ``dtype`` (as ``vits_check.serve_control``
    rounds the whole VITS graph)."""
    import jax.numpy as jnp

    def q(a):
        return a.astype(getattr(jnp, dtype)).astype(jnp.float32)

    def generator(gen, dims, z):
        plain, plain_t = vits.conv, vits.conv_transpose
        vits.conv = lambda x, p, **kw: q(plain(
            q(x), {"w": q(p["w"]), "b": q(p["b"])}, **kw))
        vits.conv_transpose = lambda x, p, stride: q(plain_t(
            q(x), {"w": q(p["w"]), "b": q(p["b"])}, stride))
        try:
            return vits.generator(gen, dims, z)
        finally:
            vits.conv, vits.conv_transpose = plain, plain_t

    return generator


def audio_numbers(config: dict, writer, rows: list, root: Path,
                  control=None) -> tuple:
    """The served samples against the reference generator over the chosen
    units' latents.  Under a control, what the generator stored in the
    control's type gives stands in for the served samples."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import vits_check

    vits = parts.load_file(root / VITS_REF)
    dims = writer.describe(config)["dims"]
    hop = vits.hop_length(dims)
    gen = jax.tree_util.tree_map(jnp.asarray,
                                 writer.reference_params(config))
    table = writer.draw(config, "unit_table")
    decode = jax.jit(lambda gen, z: vits.generator(gen, dims, z))
    low = None if control is None else jax.jit(
        lambda gen, z: low_generator(vits, control[2])(gen, dims, z))
    # one shape for every row: the largest frame bucket the program padded
    # a row to.  The drawn generator has no biases, so zeros in the latent
    # are zeros all the way up: frames past a row's end, however many, do
    # not reach its samples
    frames = max(max(int(r["dump"]["frames_bucket"]), len(r["units"]))
                 for r in rows)
    errs, ratios = [], []
    for r in rows:
        count = len(r["units"])
        z = jnp.zeros((1, frames, table.shape[1]), jnp.float32)
        z = z.at[0, :count].set(table[jnp.asarray(r["units"])])
        with jax.default_matmul_precision("highest"):
            wav = np.asarray(decode(gen, z))[0, :count * hop]
        wav_1 = np.asarray(decode(gen, z))[0, :count * hop]
        pcm = r["pcm"][:count * hop]
        if low is not None:
            cut = np.asarray(low(gen, z))[0, :count * hop]
            pcm = np.clip(cut * (32767.0 / max(float(np.max(np.abs(cut))),
                                               0.01)), -32768,
                          32767).astype(np.int16)
        errs.append(vits_check.rel_rms_error(pcm, wav))
        own = vits_check.rel_rms(vits_check.peak_normalised(wav_1, len(pcm)),
                                 vits_check.peak_normalised(wav, len(pcm)))
        if own > vits_check.NO_SINGLE_PASS:
            ratios.append(errs[-1] / own)
    numbers = {"audio_err_max": float(max(errs)),
               "audio_err_ratio_median": (float(np.median(ratios))
                                          if len(ratios) == len(rows)
                                          else None)}
    return numbers, {"audio_err_median": float(np.median(errs))}


def compare(job: dict, config: dict) -> dict:
    root = Path(job["root"])
    ref = parts.load(root, job["paths"], config, "reference")
    writer = parts.load(root, job["paths"], config, "writer")
    t0 = time.monotonic()
    rows = load_rows(job, config, root)
    hop = writer.describe(config)["samples_per_frame"]
    fpi = float(config["voice"]["units"]["frames_per_id"])
    off, sound = 0, []
    for r in rows:
        want = max(1, round(fpi * len(r["ids"])))
        d = r["dump"]
        if d is None or len(d["units"]) != want \
                or len(r["pcm"]) != want * hop \
                or len(d["routes"]) != len(r["ids"]) + want - 1:
            off += 1
            continue
        r.update(units=d["units"], logit_units=d["logit_units"],
                 tokens=list(r["ids"]) + d["units"][:-1].tolist())
        sound.append(r)
    numbers = {"rows_length_off": off}
    info = {"rows": len(rows), "rows_compared": len(sound)}
    if not sound:
        return {"numbers": numbers, "info": info}
    t_pad = _round_up(max(len(r["tokens"]) for r in sound), 128)
    hidden, routes, embed, norm_f = reference_pass(ref, writer, config,
                                                   sound, t_pad)
    reduce = reducer(ref, config)
    walks: dict = {}

    def judge(served_logits, served_routes, units) -> tuple:
        """The numbers the limits name, then the others."""
        own = logit_numbers(reduce, sound, hidden, embed, norm_f,
                            served_logits, units)
        # the pass over the served routes; a control that keeps them (and
        # the tokens) shares the run's
        if id(served_routes) not in walks:
            walks[id(served_routes)] = reference_pass(
                ref, writer, config, sound, t_pad, None, served_routes)[:2]
        walked_hidden, walked_routes = walks[id(served_routes)]
        walked = logit_numbers(reduce, sound, walked_hidden, embed, norm_f,
                               served_logits, units)
        compared = {
            "logit_err_median": own["logit_err_median"],
            "logit_err_p99": own["logit_err_p99"],
            "route_flip_share": flip_share(served_routes, routes),
            "logit_err_forced_median": walked["logit_err_median"],
            "logit_err_forced_p99": walked["logit_err_p99"],
            "route_flip_forced_share": flip_share(served_routes,
                                                  walked_routes),
            "greedy_regret_max": walked["greedy_regret_max"]}
        more = {
            "logit_err_max": own["logit_err_max"],
            "logit_err_forced_max": walked["logit_err_max"],
            "greedy_regret_p99": walked["greedy_regret_p99"],
            "greedy_regret_own_max": own["greedy_regret_max"],
            "greedy_regret_own_p99": own["greedy_regret_p99"],
            "positions_compared": own["positions_compared"],
            "steps_compared": own["steps_compared"]}
        return compared, more

    served_logits = [r["dump"]["logits"] for r in sound]
    served_routes = [r["dump"]["routes"].astype(np.int64) for r in sound]
    served_units = [r["units"] for r in sound]

    def controlled(name: str) -> tuple:
        """What stands in the program's place under a control: logits,
        routes and chosen units."""
        import jax

        if name == WRONG_UNIT:
            units = config["voice"]["units"]
            first, stop = int(units["first_id"]), int(units["stop_id"])
            wrong = np.array(served_units[0])
            wrong[-1] = first + (wrong[-1] + 1 - first) % (stop - first)
            return served_logits, served_routes, [wrong] + served_units[1:]
        low_hidden, low_routes, low_embed, low_norm = reference_pass(
            ref, writer, config, sound, t_pad, CONTROLS[name])
        logits = []
        for k, r in enumerate(sound):
            at = len(r["ids"]) - 1 + np.asarray(r["logit_units"])
            with jax.default_matmul_precision("highest"):
                logits.append(np.asarray(ref.head(
                    low_hidden[k][at], low_embed, low_norm, config)))
        return logits, [r[:len(s)] for r, s in zip(
            low_routes, served_routes)], served_units

    control = os.environ.get("PERFBENCH_CONTROL")
    compared, more = judge(*(controlled(control) if control else (
        served_logits, served_routes, served_units)))
    if control:
        info["control"] = control
    numbers.update(compared)
    info.update(more)
    controls = {name: judge(*controlled(name)) for name in os.environ.get(
        "PERFBENCH_ALSO_CONTROLS", "").split(",") if name}
    walks.clear()
    del hidden, embed
    audio, audio_info = audio_numbers(config, writer, sound, root,
                                      CONTROLS.get(control))
    numbers.update(audio)
    info.update(audio_info)
    for name, (logit, logit_more) in controls.items():
        info.setdefault("controls", {})[name] = dict(
            logit, **logit_more, **(audio_numbers(
                config, writer, sound, root, CONTROLS[name])[0]
                if name in CONTROLS else audio))
    info["numbers"] = dict(numbers)
    info.update({
        "frames_compared": int(sum(len(r["units"]) for r in sound)),
        "longest_row_frames": int(max(len(r["units"]) for r in sound)),
        "longest_row_positions": int(max(len(r["tokens"]) for r in sound)),
        "padded_positions": t_pad,
        "reference_s": time.monotonic() - t0})
    return {"numbers": numbers, "info": info}
