"""Milliseconds the step loop's one finisher thread spends on a retired
row once its vocoder program has run: the samples' transfer to the host
and their conversion (the ``kind: vocode`` dispatch spans' ``finish_ms``;
the wait for the program before is ``fetch_wait_ms`` and not counted).
Times the rows retired a second it says how near that thread is to being
the clock."""

from perfbench.harness import steps


def read(run):
    took = [v["finish_ms"] for v in steps.dispatches(run, "vocode")
            if "finish_ms" in v]
    return sum(took) / len(took) if took else None
