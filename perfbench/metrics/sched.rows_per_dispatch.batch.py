"""Sentences per device dispatch: the ``dispatch`` spans' ``sentences`` over
their ``groups`` (one group is one device program)."""


def read(run):
    spans = [s for s in run["spans"] if s["name"] == "dispatch"
             and "sentences" in s["attrs"]]
    groups = sum(int(s["attrs"].get("groups", 1)) for s in spans)
    if not groups:
        return None
    return sum(int(s["attrs"]["sentences"]) for s in spans) / groups
