"""``collective_exposed_ms``: time per traced step in which a collective
(all-gather, all-reduce, ...) runs on a chip and no other op does, for the
chip where that time is longest (``exposed_collective_s`` of
``harness/trace.py``). None where the trace holds no collective."""


def read(run):
    if run.trace is None or run.traced is None or run.traced.steps <= 0:
        return None
    devices = run.trace["devices"].values()
    if not any(d["collective_s"] > 0 for d in devices):
        return None
    worst = max(d["exposed_collective_s"] for d in devices)
    return 1e3 * worst / run.traced.steps
