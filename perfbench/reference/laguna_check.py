"""The comparison that decides ``correct`` for a unit voice with a Laguna
backbone (``laguna``) behind the stock RPCs: ``compare(job, config)`` (the
protocol: ``reference/check.py``).  ``lfm2_check.py``'s way, whose pieces
it uses: the timed path leaves, for every replayed row, the prompt's ids,
every unit chosen, the experts every token chose in every expert layer and
the float32 logits behind its first unit, its last and every 32nd between
(the last step of a row past place 512 is judged after its rings wrapped:
``info.rows_wrapped`` counts those rows); the plain reference
(``laguna_ref.py``: float32 ``highest``, no cache, no ring, the window a
mask) runs **one full forward pass** over each row's prompt + chosen units
with the same share of the routed experts (``expert_parallel.held``), one
layer's weights on the device at a time, and is held against what the
carrying step and then steps through the slot's whole caches and rings
left.  It runs twice.

**On its own routes**: ``route_flip_share``, ``logit_err_median`` /
``logit_err_p99`` (``lfm2_check.py`` says what each is).  **On the served
routes** (``forced``): ``logit_err_forced_median`` / ``logit_err_forced_p99``,
``greedy_regret_max``, ``route_flip_forced_share`` and, of the same
decisions, ``route_flip_forced_start_share``: those of every row's first
``START`` positions alone.  Whatever the routes: ``rows_length_off``,
``audio_err_max`` / ``audio_err_ratio_median``.

Controls (``PERFBENCH_CONTROL``; each has to come out as not correct), each
the reference with one fault, in the program's place: ``reference_bf16``
(what the configuration states as float32, the residual stream, every
norm's result, every product's accumulated result, rotated queries and
keys, the gate, router and attention scores and the softmax, rounded to
bfloat16: the precision below the stated one; the weights are bfloat16
already; and, as the siblings' ``reference_fp8``, the generator's weights
and convolutions in bfloat16 in the served samples' place); ``no_window``
(a sliding layer sees every earlier position: moves rows past place 512
alone); ``sliding_rule_in_full_layers`` (plain rotary on all 128 dimensions
where YaRN on 64 belongs); ``no_yarn_factor`` (``cos`` and ``sin``
unscaled); ``no_gate`` (the gate dropped); ``stale_ring`` (below the window
a sliding layer also sees, at every place past the query's, what the row
before it in the replay left there: a reused slot whose old places are
readable).  ``wrong_unit`` leaves the served path as it is and reads one
step of one row as the next unit id.  ``PERFBENCH_ALSO_CONTROLS=<names>``
leaves the run as it is and adds the controls' numbers under
``info.controls``.  ``info.numbers`` holds every number of the run, whatever
the limits file names.

What it cannot see: a fault that the kept steps do not reach (logits are
kept at every 32nd unit, the first and the last; the routes and the greedy
regret cover every step); a prompt longer than the window (none is served:
the text buckets end at 256 ids); the ring's wrap in rows of fewer than
512 positions (44 % of the traffic's rows).
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import numpy as np

from perfbench.harness import parts
from perfbench.reference import lfm2_check
from perfbench.reference.lfm2_check import WRONG_UNIT, flip_share

#: the reference's faults: what ``reference_pass`` is asked for
FAULTS = {"reference_bf16": {"round_to": "bfloat16"},
          "no_window": {"window": False},
          "sliding_rule_in_full_layers": {"full_rule": "sliding_attention"},
          "no_yarn_factor": {"yarn_factor": False},
          "no_gate": {"gate": False},
          "stale_ring": {"stale": True}}
#: the generator's storage type under ``reference_bf16`` (what
#: ``lfm2_check.audio_numbers`` reads of a control: its third entry)
LOW_GENERATOR = (None, None, "bfloat16")
#: positions at a row's start whose routing decisions are also counted
#: apart
START = 32


def held_of(bb: dict):
    share = bb.get("expert_parallel")
    return tuple(int(v) for v in share["held"]) if share else None


def reference_pass(ref, writer, config: dict, rows: list, t_pad: int, *,
                   forced=None, round_to=None, stale: bool = False,
                   **faults):
    """The reference over every row (padded to ``t_pad``: attention is
    causal, so what follows a row's end does not reach it): layers outside,
    rows inside, one layer's weights on the device at a time.  ``forced``:
    per row the experts ``[tokens, expert layers, k]`` its expert layers
    compute instead of their own choice.  The other arguments are one fault
    each (the module's docstring).  Returns per row the final hidden
    states and the experts the reference chose, then the head and the
    final norm."""
    import jax
    import jax.numpy as jnp

    def weights(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    if round_to is not None:
        # ``reduce_precision`` and not a cast there and back: inside a
        # program the TPU's compiler may keep the excess precision of such
        # a pair
        kind = jnp.finfo(getattr(jnp, round_to))
        faults["round_to"] = lambda a: jax.lax.reduce_precision(
            a, kind.nexp, kind.nmant)
    bb = writer.backbone(config)
    held = held_of(bb)
    embed = weights(writer.draw(config, "embed"))
    tokens = np.zeros((len(rows), t_pad), np.int32)
    for k, r in enumerate(rows):
        tokens[k, :len(r["tokens"])] = r["tokens"]
    lengths = [len(r["tokens"]) for r in rows]
    hidden = [embed[jnp.asarray(t)] for t in tokens]
    del embed
    if forced is not None:
        walked = []
        for f in forced:
            padded = np.zeros((t_pad,) + f.shape[1:], np.int32)
            padded[:len(f)] = f
            walked.append(padded)
    routes = [[] for _ in rows]
    fns: dict = {}
    with jax.default_matmul_precision("highest"):
        for i in range(int(bb["num_hidden_layers"])):
            # layers of one kind, head count and feed-forward share a
            # program: the first of them stands for all
            alike = (bb["layer_types"][i], bb["mlp_layer_types"][i],
                     bb["num_attention_heads_per_layer"][i])
            sparse = alike[1] != "dense"
            walk = forced is not None and sparse
            old = stale and alike[0] == "sliding_attention"
            if (alike, walk) not in fns:
                fns[alike, walk] = (jax.jit(
                    lambda h, p, f=None, other=None, i=i: ref.layer(
                        h, p, i, bb, held,
                        dict(faults, stale=other) if other is not None
                        else faults, f)),
                    jax.jit(lambda h, p, i=i: ref.left(h, p, i, bb)))
            layer, left = fns[alike, walk]
            p = weights(writer.draw_layer(config, i))
            # what each row's predecessor left, before any row moves on
            before = [(*left(hidden[k - 1], p), jnp.int32(lengths[k - 1]))
                      for k in range(len(rows))] if old else None
            for k in range(len(rows)):
                args = (walked[k][:, len(routes[k])],) if walk else \
                    (None, before[k]) if old else ()
                hidden[k], chosen = layer(hidden[k], p, *args)
                if chosen is not None:
                    routes[k].append(np.asarray(chosen))
            del p, before
    return (hidden, [np.stack(r, 1) for r in routes],
            weights(writer.draw(config, "head")),
            weights(writer.draw(config, "norm_f")))


def start_flip_share(served: list, reference: list) -> float:
    """``flip_share`` over every row's first ``START`` positions."""
    return flip_share([s[:START] for s in served],
                      [r[:START] for r in reference])


def compare(job: dict, config: dict) -> dict:
    root = Path(job["root"])
    ref = parts.load(root, job["paths"], config, "reference")
    writer = parts.load(root, job["paths"], config, "writer")
    t0 = time.monotonic()
    rows = lfm2_check.load_rows(job, config, root)
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
    t_pad = lfm2_check._round_up(max(len(r["tokens"]) for r in sound), 128)
    hidden, routes, head, norm_f = reference_pass(ref, writer, config, sound,
                                                  t_pad)
    # ``ref.head`` takes the head's own matrix where ``lfm2_ref.head``
    # takes the embedding: the reducer hands on whichever it is given
    reduce = lfm2_check.reducer(ref, config)
    walks: dict = {}

    def judge(served_logits, served_routes, units) -> tuple:
        """The numbers the limits name, then the others."""
        own = lfm2_check.logit_numbers(reduce, sound, hidden, head, norm_f,
                                       served_logits, units)
        # the pass over the served routes; a control that keeps them (and
        # the tokens) shares the run's
        if id(served_routes) not in walks:
            walks[id(served_routes)] = reference_pass(
                ref, writer, config, sound, t_pad, forced=served_routes)[:2]
        walked_hidden, walked_routes = walks[id(served_routes)]
        walked = lfm2_check.logit_numbers(reduce, sound, walked_hidden, head,
                                          norm_f, served_logits, units)
        compared = {
            "logit_err_median": own["logit_err_median"],
            "logit_err_p99": own["logit_err_p99"],
            "route_flip_share": flip_share(served_routes, routes),
            "logit_err_forced_median": walked["logit_err_median"],
            "logit_err_forced_p99": walked["logit_err_p99"],
            "route_flip_forced_share": flip_share(served_routes,
                                                  walked_routes),
            "route_flip_forced_start_share": start_flip_share(
                served_routes, walked_routes),
            "greedy_regret_max": walked["greedy_regret_max"]}
        more = {
            "logit_err_max": own["logit_err_max"],
            "logit_err_forced_max": walked["logit_err_max"],
            "greedy_regret_p99": walked["greedy_regret_p99"],
            "greedy_regret_own_max": own["greedy_regret_max"],
            "greedy_regret_own_p99": own["greedy_regret_p99"],
            "route_flip_start_share": start_flip_share(served_routes,
                                                       routes),
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
        low_hidden, low_routes, low_head, low_norm = reference_pass(
            ref, writer, config, sound, t_pad, **FAULTS[name])
        logits = []
        for k, r in enumerate(sound):
            at = len(r["ids"]) - 1 + np.asarray(r["logit_units"])
            with jax.default_matmul_precision("highest"):
                logits.append(np.asarray(ref.head(
                    low_hidden[k][at], low_head, low_norm, config)))
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
    del hidden
    low = {"reference_bf16": LOW_GENERATOR}
    audio, audio_info = lfm2_check.audio_numbers(config, writer, sound, root,
                                                 low.get(control))
    numbers.update(audio)
    info.update(audio_info)
    for name, (logit, logit_more) in controls.items():
        info.setdefault("controls", {})[name] = dict(
            logit, **logit_more, **(lfm2_check.audio_numbers(
                config, writer, sound, root, low[name])[0]
                if name in low else audio))
    info["numbers"] = dict(numbers)
    window = int(config["sliding_window"])
    info.update({
        "rows_wrapped": int(sum(len(r["tokens"]) > window for r in sound)),
        "frames_compared": int(sum(len(r["units"]) for r in sound)),
        "longest_row_frames": int(max(len(r["units"]) for r in sound)),
        "longest_row_positions": int(max(len(r["tokens"]) for r in sound)),
        "padded_positions": t_pad,
        "reference_s": time.monotonic() - t0})
    return {"numbers": numbers, "info": info}
