"""Host milliseconds a device group costs while the host is not blocked on
the device: ``enqueue`` (pad, transfer, asynchronous dispatch) and
``epilogue`` (dequantise and slice) of
``sonata_dispatch_host_seconds_total`` over ``sonata_dispatch_groups_total``,
in the window.  (Where more programs are queued than the runtime keeps in
flight, the enqueue's jitted call waits for a slot, and this reads that
wait: PERF.md, "Where the time goes".)"""

from perfbench.harness import counters


def read(run):
    name = "sonata_dispatch_host_seconds_total"
    groups = counters.window(run, "sonata_dispatch_groups_total")
    busy = [counters.window(run, name, f'phase="{p}"')
            for p in ("enqueue", "epilogue")]
    if not groups or any(v is None for v in busy):
        return None
    return sum(busy) * 1e3 / groups
