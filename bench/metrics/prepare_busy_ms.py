"""``prepare_busy_ms``: host time the pipeline's prepare stage spent per
step in the window (dedup plans, host_lru fault-in and write-back), from
``PipelinedTrainer.pipeline_metrics()['pipeline/prepare/busy_s']``."""


def read(run):
    busy = run.counters.get("pipeline/prepare/busy_s")
    if busy is None or run.window.steps <= 0:
        return None
    return 1e3 * busy / run.window.steps
