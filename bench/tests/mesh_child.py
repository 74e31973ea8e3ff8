"""One run of the four-chip cell, cut to a CPU size, on four virtual CPU
devices: ``python -m bench.tests.mesh_child [fault]`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. The last line of
its output is one JSON object: the run's verdict and checks, its window,
and how the trainer's tables and a batch lie over the devices."""
import json
import sys

import jax

from bench import control
from bench import run as R
from bench.harness import program

from .conftest import CPU_LIMITS, CPU_PEAKS, small_cell

SEED = 2**31 + 7
CELL = "criteo-dense-zipf-4chip"


def layout(cell, devices) -> dict:
    """Devices and rows per device of a table after init, and of a batch
    as the step takes it."""
    system = program.System(cell.config, cell.batch, devices)
    b = cell.batches(SEED, 0, 1)[0]
    system.init(SEED, b)
    table = system.state.emb["field_00"]["table"]
    ids = system.feed(b)["ids"]
    return {"table_devices": len(table.sharding.device_set),
            "table_rows": [s.data.shape[0] for s in table.addressable_shards],
            "batch_rows": [s.data.shape[0] for s in ids.addressable_shards]}


def main(fault: str | None) -> dict:
    devices = jax.devices()[:4]
    cell = small_cell(CELL, rows=5000, batch=64, limits=CPU_LIMITS)
    out = {"chips": cell.chips, "batch": cell.batch}
    if fault is None:
        out.update(layout(cell, devices))
    else:
        control.FAULTS[fault](setattr)
    res = R.execute(cell, SEED, 0.3, False, devices, CPU_PEAKS)
    out.update(correct=res["correct"], checks=res["checks"],
               window=res["window"],
               samples_per_s=res["metrics"]["train_samples_per_s"]["value"])
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1] if len(sys.argv) > 1 else None)))
