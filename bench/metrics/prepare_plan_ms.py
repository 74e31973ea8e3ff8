"""``prepare_plan_ms``: host prepare time per step in the window spent on the
dedup plans (``np.unique`` over each table's ids) and their copies to the
device, summed over the tables:
``PipelinedTrainer.pipeline_metrics()['pipeline/prepare/plan_s']``, the
``persia/prepare/plan`` spans. None where the program reports no such phase."""


def read(run):
    s = run.counters.get("pipeline/prepare/plan_s")
    if s is None or run.window.steps <= 0:
        return None
    return 1e3 * s / run.window.steps
