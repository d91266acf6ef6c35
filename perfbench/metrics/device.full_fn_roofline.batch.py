"""The full-pipeline programs' share of their roofline: the least time the
chip could take for the shapes dispatched (operations and bytes counted by
``perfbench/harness/costs.py`` from the configuration's sizes, against the
table of peaks), over the device time of those programs in the trace.  At
these shapes the bound is bytes."""

from perfbench.harness import costs, shapes


def read(run):
    programs = shapes.programs(run)
    if not programs:
        return None
    peak = costs.peaks(run["device"]["kind"])
    t = shapes.text_bucket(run)
    speakers = run["config"]["voice"]["num_speakers"]
    least = sum(costs.roofline(costs.full_fn_cost(
        run["dims"], p["b"], t, p["f"], speakers), peak)["seconds"]
        for p in programs)
    took = sum(p["seconds"] for p in programs)
    return 100.0 * least / took if took else None
