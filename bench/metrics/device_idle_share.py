"""``device_idle_share``: the share of the traced window in which no
operation ran on a chip (one minus the union of its ``XLA Ops`` intervals
over the window), for the chip that idled most."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * max(d["idle_share"] for d in run.trace["devices"].values())
