"""The comparison that decides ``correct`` for a unit voice with a
GigaChat-3.5 backbone (``gigachat3_5``) behind the stock RPCs:
``compare(job, config)`` (the protocol: ``reference/check.py``).
``nemotron_check.py``'s way, whose pieces (and ``lfm2_check.py``'s) it
uses: the timed path leaves, for every replayed row, the prompt's ids, every
unit chosen, the experts every token chose in every expert layer, the
float32 logits (over the rows of the vocabulary held here) behind its first
unit, its last and every 32nd between, and the delta-rule state the row
left in the last linear layer; the plain reference (``gigachat_ref.py``:
float32 ``highest``, the linear layers as the recurrence over positions,
latent attention per head, no cache) runs **one full forward pass** over
each row's prompt + chosen units with the same share of the routed experts
(``expert_parallel.held``) and of the vocabulary, one layer's weights on
the device at a time, and is held against what the carrying step (chunked
and expanded forms) and then steps through the slot's states and latent
rows (the recurrence, the absorbed form) left.  It runs twice.

**On its own routes**: ``route_flip_share``, ``logit_err_median`` /
``logit_err_p99`` (``lfm2_check.py`` says what each is).  **On the served
routes** (``forced``): ``logit_err_forced_median`` / ``logit_err_forced_p99``,
``greedy_regret_max``, ``route_flip_forced_share`` and, of the same
decisions, ``route_flip_forced_start_share``: those of every row's first
``START`` positions alone, where what a row before it left in the slot's
state would show (latent rows are masked by position; a state is not, and
it fades over tens to hundreds of positions); and ``state_err_p99``: over
every row and value head, the distance of the state the row left in the
last linear layer from the reference's after the row's last token, relative
to the reference's norm.  Whatever the routes: ``rows_length_off``,
``audio_err_max`` / ``audio_err_ratio_median``.

Controls (``PERFBENCH_CONTROL``; each has to come out as not correct), each
the reference with one fault, in the program's place: ``reference_bf16``
(what the configuration states as float32, the residual stream, every
norm's and every product's result, router and attention scores, the softmax
and the delta-rule state after every position, rounded to bfloat16: the
precision below the stated one; the weights are bfloat16 already; and the
generator's weights and convolutions in bfloat16 in the served samples'
place); ``no_delta`` (the update without ``- alpha S^T k``: a plain gated
outer-product state); ``no_decay`` (``alpha`` held at 1); ``stale_state``
(every row's linear layers start from what the row before it in the replay
left: a slot not written whole by the prefill); ``no_attn_gate`` (the full
layers' ``sigmoid(x W_g)`` left out); ``plain_norm`` (a norm's weight ``w``
taken for its gain in ``2 sigmoid(w)``'s place); ``no_post_norm`` (the
mixer's result added without its norm); ``no_clamp`` (every SwiGLU
unclamped); and, beyond those, ``state_bf16`` (the state alone rounded to
bfloat16 after every position).  ``wrong_unit`` leaves the served path as
it is and reads one step of one row as the next unit id.
``PERFBENCH_ALSO_CONTROLS=<names>`` leaves the run as it is and adds the
controls' numbers under ``info.controls``.  ``info.numbers`` holds every
number of the run, whatever the limits file names.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import numpy as np

from perfbench.harness import parts
from perfbench.reference import lfm2_check
from perfbench.reference.lfm2_check import WRONG_UNIT, flip_share
from perfbench.reference.nemotron_check import held_of, start_flip_share, \
    state_errors

#: the reference's faults: what ``reference_pass`` is asked for
FAULTS = {"reference_bf16": {"round_to": "bfloat16"},
          "state_bf16": {"round_state": "bfloat16"},
          "no_delta": {"delta": False},
          "no_decay": {"decay": False},
          "stale_state": {"stale": True},
          "no_attn_gate": {"attn_gate": False},
          "plain_norm": {"plain_norm": True},
          "no_post_norm": {"post_norm": False},
          "no_clamp": {"clamp": False}}
#: the generator's storage type under ``reference_bf16`` (what
#: ``lfm2_check.audio_numbers`` reads of a control: its third entry)
LOW_GENERATOR = (None, None, "bfloat16")


def reference_pass(ref, writer, config: dict, rows: list, t_pad: int, *,
                   forced=None, stale: bool = False, **faults):
    """The reference over every row (padded to ``t_pad``: every mixer is
    causal, so what follows a row's end does not reach it): layers outside,
    rows inside, one layer's weights on the device at a time.  ``forced``:
    per row the experts ``[tokens, expert layers, k]`` its expert layers
    compute instead of their own choice.  The other arguments are one fault
    each (the module's docstring).  Returns per row the final hidden
    states, the experts the reference chose and the state the row's last
    token left in the last linear layer, then the head and the final
    norm."""
    import jax
    import jax.numpy as jnp

    def weights(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    for name in ("round_to", "round_state"):
        if faults.get(name) is not None:
            # ``reduce_precision`` and not a cast there and back: inside a
            # program the TPU's compiler may keep the excess precision of
            # such a pair
            kind = jnp.finfo(getattr(jnp, faults[name]))
            faults[name] = lambda a, kind=kind: jax.lax.reduce_precision(
                a, kind.nexp, kind.nmant)
    bb = writer.backbone(config)
    held = held_of(bb)
    embed = weights(writer.draw(config, "embed"))
    tokens = np.zeros((len(rows), t_pad), np.int32)
    for k, r in enumerate(rows):
        tokens[k, :len(r["tokens"])] = r["tokens"]
    hidden = [embed[jnp.asarray(t)] for t in tokens]
    del embed
    if forced is not None:
        walked = []
        for f in forced:
            padded = np.zeros((t_pad,) + f.shape[1:], np.int32)
            padded[:len(f)] = f
            walked.append(padded)
    routes = [[] for _ in rows]
    states = [None] * len(rows)
    lengths = [jnp.int32(len(r["tokens"])) for r in rows]
    fns: dict = {}
    with jax.default_matmul_precision("highest"):
        left = jax.jit(lambda h, p: ref.left(h, p, bb))
        for i in range(int(bb["num_hidden_layers"])):
            full, dense = kind = ref.kind_of(i, bb)
            walk = forced is not None and not dense
            old = stale and not full
            if kind not in fns:     # layers of one kind: one compile
                fns[kind] = jax.jit(
                    lambda h, p, n, f=None, s=None, i=i: ref.layer(
                        h, p, i, bb, held, faults, f, s, n))
            fn = fns[kind]
            p = weights(writer.draw_layer(config, i))
            # what each row's predecessor left, before any row moves on
            before = [left(hidden[k - 1], p) for k in range(len(rows))] \
                if old else None
            for k in range(len(rows)):
                args = (walked[k][:, len(routes[k])],) if walk else \
                    (None, before[k]) if old else ()
                hidden[k], chosen, state = fn(hidden[k], p, lengths[k],
                                              *args)
                if chosen is not None:
                    routes[k].append(np.asarray(chosen))
                if state is not None:
                    states[k] = np.asarray(state[0])
            del p, before
    return (hidden, [np.stack(r, 1) for r in routes], states,
            weights(writer.draw(config, "head")),
            weights(writer.draw(config, "norm_f")))


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
                or len(d["routes"]) != len(r["ids"]) + want - 1 \
                or "state" not in d:
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
    hidden, routes, _, head, norm_f = reference_pass(ref, writer, config,
                                                     sound, t_pad)
    # ``ref.head`` takes the head's own matrix where ``lfm2_ref.head``
    # takes the embedding: the reducer hands on whichever it is given
    reduce = lfm2_check.reducer(ref, config)
    walks: dict = {}

    def judge(served_logits, served_routes, units, served_states) -> tuple:
        """The numbers the limits name, then the others."""
        own = lfm2_check.logit_numbers(reduce, sound, hidden, head, norm_f,
                                       served_logits, units)
        # the pass over the served routes; a control that keeps them (and
        # the tokens) shares the run's
        # (the routes are kept beside their walk: a control's list that
        # was let go would hand its id to the next control's)
        if id(served_routes) not in walks:
            walks[id(served_routes)] = (served_routes, reference_pass(
                ref, writer, config, sound, t_pad,
                forced=served_routes)[:3])
        walked_hidden, walked_routes, walked_states = walks[
            id(served_routes)][1]
        state_errs = state_errors(served_states, walked_states)
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
            "state_err_p99": float(np.quantile(state_errs, 0.99)),
            "greedy_regret_max": walked["greedy_regret_max"]}
        more = {
            "logit_err_max": own["logit_err_max"],
            "logit_err_forced_max": walked["logit_err_max"],
            "greedy_regret_p99": walked["greedy_regret_p99"],
            "greedy_regret_own_max": own["greedy_regret_max"],
            "greedy_regret_own_p99": own["greedy_regret_p99"],
            "route_flip_start_share": start_flip_share(served_routes,
                                                       routes),
            "state_err_median": float(np.median(state_errs)),
            "state_err_max": float(state_errs.max()),
            "positions_compared": own["positions_compared"],
            "steps_compared": own["steps_compared"]}
        return compared, more

    served_logits = [r["dump"]["logits"] for r in sound]
    served_routes = [r["dump"]["routes"].astype(np.int64) for r in sound]
    served_units = [r["units"] for r in sound]
    served_states = [r["dump"]["state"] for r in sound]

    def controlled(name: str) -> tuple:
        """What stands in the program's place under a control: logits,
        routes and chosen units."""
        import jax

        if name == WRONG_UNIT:
            units = config["voice"]["units"]
            first, stop = int(units["first_id"]), int(units["stop_id"])
            wrong = np.array(served_units[0])
            wrong[-1] = first + (wrong[-1] + 1 - first) % (stop - first)
            return (served_logits, served_routes,
                    [wrong] + served_units[1:], served_states)
        low_hidden, low_routes, low_states, low_head, low_norm = \
            reference_pass(ref, writer, config, sound, t_pad, **FAULTS[name])
        logits = []
        for k, r in enumerate(sound):
            at = len(r["ids"]) - 1 + np.asarray(r["logit_units"])
            with jax.default_matmul_precision("highest"):
                logits.append(np.asarray(ref.head(
                    low_hidden[k][at], low_head, low_norm, config)))
        return logits, [r[:len(s)] for r, s in zip(
            low_routes, served_routes)], served_units, low_states

    control = os.environ.get("PERFBENCH_CONTROL")
    compared, more = judge(*(controlled(control) if control else (
        served_logits, served_routes, served_units, served_states)))
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
    info.update({
        "frames_compared": int(sum(len(r["units"]) for r in sound)),
        "longest_row_frames": int(max(len(r["units"]) for r in sound)),
        "longest_row_positions": int(max(len(r["tokens"]) for r in sound)),
        "padded_positions": t_pad,
        "reference_s": time.monotonic() - t0})
    return {"numbers": numbers, "info": info}
