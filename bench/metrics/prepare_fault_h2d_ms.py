"""``prepare_fault_h2d_ms``: host prepare time per step in the window spent on
the fault-in's padding, host-to-device copies and scatter dispatch, summed
over the tables:
``PipelinedTrainer.pipeline_metrics()['pipeline/prepare/fault_h2d_s']``, the
``persia/prepare/fault_h2d`` spans. None where the program reports no such
phase."""


def read(run):
    s = run.counters.get("pipeline/prepare/fault_h2d_s")
    if s is None or run.window.steps <= 0:
        return None
    return 1e3 * s / run.window.steps
