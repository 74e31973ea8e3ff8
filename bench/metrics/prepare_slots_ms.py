"""``prepare_slots_ms``: host prepare time per step in the window spent on
slot-map upkeep: slot lookup, victim choice, map and clock updates, id
translation, pinning, summed over the tables:
``PipelinedTrainer.pipeline_metrics()['pipeline/prepare/slots_s']``, the
``persia/prepare/slots`` spans. None where the program reports no such
phase."""


def read(run):
    s = run.counters.get("pipeline/prepare/slots_s")
    if s is None or run.window.steps <= 0:
        return None
    return 1e3 * s / run.window.steps
