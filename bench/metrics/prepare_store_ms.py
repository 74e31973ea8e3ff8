"""``prepare_store_ms``: host prepare time per step in the window spent on host
store reads of faulted rows and write-backs of evicted rows, summed over the
tables: ``PipelinedTrainer.pipeline_metrics()['pipeline/prepare/store_s']``,
the ``persia/prepare/store`` spans. None where the program reports no such
phase."""


def read(run):
    s = run.counters.get("pipeline/prepare/store_s")
    if s is None or run.window.steps <= 0:
        return None
    return 1e3 * s / run.window.steps
