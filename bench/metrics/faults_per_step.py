"""``faults_per_step``: rows moved from the host store into the device
caches per step in the window, summed over the tables (the change of the
host_lru backends' ``faults`` counters)."""


def read(run):
    faults = run.counters.get("faults")
    if faults is None or run.window.steps <= 0:
        return None
    return faults / run.window.steps
