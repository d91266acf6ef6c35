"""Share of the frames the device computed that nobody asked for: padded
rows times padded frames of the programs in the traced interval, against
the frames of the audio served by the requests that ended in it.  (The
stock server's dispatch spans carry no bucket attributes, so the padded
shapes are read off the device trace.)"""

from perfbench.harness import shapes


def read(run):
    programs = shapes.programs(run)
    if not programs:
        return None
    padded = sum(p["b"] * p["f"] for p in programs)
    a, b = shapes.traced_interval(run)
    hop = shapes.hop(run)
    gen = run["generator"]
    served = sum(sum(r["samples"]) for r in gen["records"]
                 if a <= gen["wall_origin"] + r["t_end"] <= b) / hop
    if not padded or not served:
        return None
    return max(0.0, 100.0 * (1.0 - served / padded))
