"""Share of the frames the device computed in the window that nobody asked
for, by the program's own count: everything ``sonata_dispatch_frames_total``
holds but ``served`` (rows shorter than their group's longest, the frame
budget's headroom, the bucket's step, dummy rows, clipped programs that
were rerun).  The whole window, where ``sched.padding_share.batch`` reads
the traced seconds off the device."""

from perfbench.harness import counters


def read(run):
    return counters.frame_share(run, [p for p in counters.FRAME_PARTS
                                      if p != "served"])
