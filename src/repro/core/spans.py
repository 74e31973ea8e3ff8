"""Host spans on the profiler's clock, each kept as a running counter too.

``span(name, into, **args)`` opens ``jax.profiler.TraceAnnotation(
"persia/<name>", **args)`` and, when ``into`` is a dict, adds the same
``perf_counter`` interval to ``into[name]``. One interval feeds both: the
trace viewer's host line (and any idle-gap attribution over it) and the
counters a caller reports, e.g. ``PipelinedTrainer.pipeline_metrics()``.

Names are few and fixed; what varies (a table, a row count) goes in as an
argument, which the profiler records as a stat of the event and never as
part of its name. With the profiler off a span costs about 2 us of host
time.

    persia/stage/<stage>    a pipeline stage's busy interval (core/pipeline.py)
    persia/prepare/<phase>  one phase of the host prepare, per table
                            (PREPARE_PHASES; core/backend.py, core/pipeline.py)
    persia/step             the fused step's host call (core/hybrid.py)
    persia/prepare          the fused step's prepare
    persia/step/dispatch    the fused step's jitted call
"""
from __future__ import annotations

import time

import jax

PREFIX = "persia/"

# the host prepare's phases, in the order they run for one table: the
# dedup plan; the wait for the pipeline's table-store lock; slot-map upkeep
# (lookup, victim choice, map and clock updates, id translation, pinning);
# the eviction gather and its blocking device-to-host read; host store
# reads and write-backs; the fault-in's host-to-device copies and scatter
PREPARE_PHASES = ("plan", "lock_wait", "slots", "evict_d2h", "store",
                  "fault_h2d")


class span:
    """Context manager: a named host span, its seconds added to ``into``."""

    __slots__ = ("name", "into", "_ann", "_t0")

    def __init__(self, name: str, into: dict | None = None, **args):
        self.name = name
        self.into = into
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **args)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self.into is not None:
            self.into[self.name] = self.into.get(self.name, 0.0) + dt

