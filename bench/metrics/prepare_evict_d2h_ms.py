"""``prepare_evict_d2h_ms``: host prepare time per step in the window spent on
the eviction gathers and their blocking device-to-host reads of evicted rows
and accumulators, summed over the tables:
``PipelinedTrainer.pipeline_metrics()['pipeline/prepare/evict_d2h_s']``, the
``persia/prepare/evict_d2h`` spans. None where the program reports no such
phase."""


def read(run):
    s = run.counters.get("pipeline/prepare/evict_d2h_s")
    if s is None or run.window.steps <= 0:
        return None
    return 1e3 * s / run.window.steps
