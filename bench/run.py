#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration and traffic
mix are files under ``bench/`` named there. A run:

1. draws its model from ``--seed``; its traffic is a stream of distinct
   batches from the same seed, drawn ahead of each phase;
2. builds the trainer the configuration names and steps it through its
   first steps, reading what the check compares; warms up (until the host
   store has taken write-backs, where the tables have one) and draws the
   batches the window will need at the warm-up's pace. All of this,
   compilation included, is the set-up, ``setup_s``;
3. trains for ``--seconds`` through the same call and the same object, and
   takes ``train_samples_per_s`` and ``step_p90_ms`` from the host clock;
4. with ``--trace 1``, traces a few more seconds and reduces the trace and
   the program's counters to the cell's per-layer metrics;
5. frees the program's state and runs the plain reference over the compared
   steps; ``correct`` is the comparison against the configuration's limits.

A cell on several chips runs on a mesh over them (``harness/program.py``);
its batch is the batch per chip times the chips, and the reference follows
that global batch on one chip.

The last line of standard output is one JSON object. Without a TPU, or with
fewer chips than the cell needs, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(CHECKOUT, "src"))
sys.path.insert(0, CHECKOUT)

TRACE_SECONDS = 2.0     # traced after the window, with --trace 1
PACE_SECONDS = 0.5      # a first short warm-up that sets the pace
WARM_SECONDS = 2.0      # the warm-up right before the window
FIRST_DRAW = 64         # batches drawn for the first warm-up
WRITEBACK_STEPS = 64    # at most this many steps wait for write-backs
DRAW_MARGIN = 1.25      # batches drawn ahead, over what the pace asks


def log(msg: str):
    """A progress line on standard error, stamped with the run's age."""
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache`` (the fixed path the
    program's launchers use). Every program is kept, however fast it
    compiled, so a second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(CHECKOUT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax
        self.counts = {"traces": 0, "compiles": 0}
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import spec
    try:
        cell = spec.resolve(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if cell.chips > 1 and cell.config["trainer"]["path"] != "fused":
        print(f"bench: {cell.name} asks for {cell.chips} chips on the "
              f"{cell.config['trainer']['path']!r} path; only the fused path "
              "runs on a mesh", file=sys.stderr)
        return 2
    from bench.harness import device
    devices = device.require_tpu(cell.chips)
    try:
        import repro  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 3
    enable_compile_cache()
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                  device.peaks(devices[0].device_kind))
    for n, c in out["checks"].items():
        print(f"check {n}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


class Stream:
    """The cell's traffic by position. Set-up draws batches ahead
    (``ensure``); a batch asked for before it was drawn is drawn then, and
    counted in ``late``."""

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.batches = []
        self.late = 0

    def ensure(self, end: int):
        n = end - len(self.batches)
        if n > 0:
            self.batches += self.cell.batches(self.seed, len(self.batches), n)

    def __getitem__(self, i: int) -> dict:
        if i >= len(self.batches):
            self.late += i + 1 - len(self.batches)
            self.ensure(i + 1)
        return self.batches[i]


def compared_steps(system, batches: list, n_fields: int) -> dict:
    """Step ``system`` through ``batches`` by the window's own call and read
    what the check compares: each step's loss, the first gradient as the
    optimizers received it, and the norm of each leaf's change (tower
    leaves; the rows the batches touch in each table, read at that field's
    own width, ``reference.padded``; and, of a host-backed table, those of
    them gone from its device cache, read from its host store)."""
    import numpy as np
    from bench.harness import reference
    touched = reference.touched(batches, n_fields)
    field = {reference.table_name(f): f for f in range(n_fields)}
    ids = {reference.table_name(f): t for f, t in
           enumerate(reference.padded(touched))}
    tower0, rows0 = system.tower(), system.rows(ids)
    log("initial rows read")
    losses, grad1 = [], {}
    for k, b in enumerate(batches):
        losses.append(system.step_once(b))
        if k == 0:
            grad1 = system.first_grads()
    change = {n: float(np.linalg.norm(v - tower0[n]))
              for n, v in system.tower().items()}
    for n, v in system.rows(ids).items():
        change[f"emb/{n}"] = float(np.linalg.norm(v - rows0[n]))
    subsets = {}
    for n, (x, rows) in system.stored(ids).items():
        f = field[n]
        at = np.searchsorted(touched[f], x)
        change[f"store/{n}"] = float(np.linalg.norm(rows - rows0[n][at]))
        subsets[f"store/{n}"] = (f, x)
    return {"losses": losses, "grad1": grad1, "change": change,
            "subsets": subsets}


def execute(cell, seed: int, seconds: float, traced_run: bool, devices,
            peaks: dict) -> dict:
    """One run of ``cell`` on ``devices``: the result line as a dict."""
    counter = CompileCounter()
    import jax
    import numpy as np
    from bench.harness import (compare, device, program, reference, spec,
                               trace, work)

    cfg = cell.config
    model = cfg["model"]
    F = model["n_id_fields"]
    batch = cell.batch
    K = int(cfg["check"]["steps"])
    stream = Stream(cell, seed)
    stream.ensure(K + FIRST_DRAW)
    log(f"{len(stream.batches)} batches of {batch} drawn")

    # -- set-up: the model, the compared steps, the warm-up ------------------
    system = program.System(cfg, batch, devices)
    system.init(seed, stream[0])
    log("model drawn")
    prog = compared_steps(system, stream.batches[:K], F)
    log(f"{K} compared steps taken")

    nxt = K
    if "writebacks" in system.counters():
        while system.counters()["writebacks"] == 0 and \
                nxt < K + WRITEBACK_STEPS:
            nxt += system.run(stream, nxt, PACE_SECONDS).steps
    for warm in (PACE_SECONDS, WARM_SECONDS):
        w = system.run(stream, nxt, warm)
        nxt += w.steps
        ahead = seconds + (TRACE_SECONDS if traced_run else 0.0) \
            if warm == WARM_SECONDS else WARM_SECONDS
        stream.ensure(nxt + 8 + math.ceil(
            DRAW_MARGIN * ahead * w.steps / w.seconds))
    late = stream.late
    log(f"warmed up after {nxt} steps; {len(stream.batches)} batches drawn")

    # -- the measured window --------------------------------------------------
    before = system.counters()
    counter.active = True
    t_window = time.perf_counter()
    win = system.run(stream, nxt, seconds)
    counter.active = False
    nxt += win.steps
    after = system.counters()
    setup_s = t_window - T_START
    log(f"window: {win.steps} steps in {win.seconds:.3f} s")
    counters = {k: after[k] - before.get(k, 0.0) for k in
                ("faults", "writebacks") if k in after}
    counters.update({k: v for k, v in after.items()
                     if k.startswith("pipeline/")})
    window_late = stream.late - late

    red = None
    traced = None
    if traced_run:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(trace.WINDOW):
                    traced = system.run(stream, nxt, TRACE_SECONDS,
                                        annotate=True)
            finally:
                jax.profiler.stop_trace()
            log(f"traced {traced.steps} steps")
            red = trace.reduce(trace.TraceData.from_xplane(tdir),
                               steps=traced.steps)
            log("trace reduced")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    mem_peak = device.memory_peak_bytes(devices)
    window_counts = dict(counter.counts)
    system.free()

    # -- metrics --------------------------------------------------------------
    flops = cell.tower.flops_per_sample(model, spec.tower_input(cfg))
    values = {}
    if not traced_run:
        values["train_samples_per_s"] = win.samples / win.seconds
        values["step_p90_ms"] = 1e3 * statistics.quantiles(
            win.gaps_s, n=10, method="inclusive")[8]
        values["setup_s"] = setup_s
        wanted = cell.end_to_end
    else:
        tau = cfg["tables"]["staleness"]
        uniq = {}
        for i in traced.consumed:
            for j in (i, i - tau):
                if j not in uniq:
                    uniq[j] = work.unique_counts(stream[j]["ids"], F)
        emb_b = sum(work.emb_bytes(uniq[i], uniq[i - tau], model["emb_dim"])
                    for i in traced.consumed)
        ctx = types.SimpleNamespace(chips=cell.chips, peaks=peaks,
                                    model=model,
                                    tower_flops_per_sample=flops, window=win,
                                    trace=red, traced=traced,
                                    emb_bytes_traced=emb_b,
                                    counters=counters)
        for m in cell.per_layer:
            reader = spec.load("metrics", m["name"])
            v = reader.read(ctx)
            if v is not None:
                values[m["name"]] = v
        wanted = cell.per_layer
    metrics = {m["name"]: {"value": float(values[m["name"]]),
                           "unit": m["unit"]}
               for m in wanted if m["name"] in values}

    # -- the reference and the comparison -------------------------------------
    compared = stream.batches[:K]
    del stream
    ref = reference.Reference(cfg, seed, cell.tower).run(compared,
                                                         prog["subsets"])
    gaps = compare.gaps(prog, ref)
    log("reference run")
    ok, checks = compare.verdict(gaps, cfg["check"]["limits"])
    failed = sum(1 for x in win.losses if not np.isfinite(x))
    correct = ok and failed == 0 and win.steps > 0

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = mem_peak
    if red is not None:
        dev["busy_s"] = statistics.mean(
            d["busy_s"] for d in red["devices"].values())
        dev["window_s"] = red["window_s"]
    out = {"correct": bool(correct), "attempted": win.steps,
           "failed": failed, "metrics": metrics, "device": dev}
    if red is not None:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["window"] = {"steps": win.steps, "seconds": win.seconds,
                     "compiles": window_counts["compiles"],
                     "traces": window_counts["traces"],
                     "drawn_late": window_late,
                     "losses_first_last": [win.losses[0], win.losses[-1]]
                     if win.losses else []}
    out["compared"] = {"steps": K,
                       "program_losses": prog["losses"],
                       "reference_losses": ref["losses"],
                       "stored_rows": sum(len(x) for _, x
                                          in prog["subsets"].values()),
                       "grad1_gap_at": gaps["grad1_gap_at"],
                       "change_gap_at": gaps["change_gap_at"],
                       "leaves_left_out": gaps["leaves_left_out"]}
    out["checks"] = checks
    return out


if __name__ == "__main__":
    sys.exit(main())
