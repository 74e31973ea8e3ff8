"""``prepare_lock_wait_ms``: host prepare time per step in the window spent on
waiting for the pipeline's table-store lock, which the lookup and put stages
hold while they dispatch, summed over the tables:
``PipelinedTrainer.pipeline_metrics()['pipeline/prepare/lock_wait_s']``, the
``persia/prepare/lock_wait`` spans. None where the program reports no such
phase."""


def read(run):
    s = run.counters.get("pipeline/prepare/lock_wait_s")
    if s is None or run.window.steps <= 0:
        return None
    return 1e3 * s / run.window.steps
