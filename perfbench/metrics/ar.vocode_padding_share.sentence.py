"""Share of the frames the vocoder programs computed that no row needed: a
retired row runs alone at its frame bucket, so this is
``1 - sum(frames_needed) / sum(frames_bucket)`` over the window's
``kind: vocode`` dispatch spans (a program without them: nothing)."""

from perfbench.harness import steps


def read(run):
    rows = [v for v in steps.dispatches(run, "vocode")
            if "frames_needed" in v]
    computed = sum(v["frames_bucket"] * v["rows"] for v in rows)
    if not computed:
        return None
    return 100.0 * (1.0 - sum(v["frames_needed"] for v in rows) / computed)
