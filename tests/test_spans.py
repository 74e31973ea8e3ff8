"""Host spans and counters (core/spans.py): the pipeline's per-phase
prepare seconds, the spans on the profiler's host clock, the named scopes
inside the jitted programs, and the lost-put counter."""
import glob

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.core import adapters
from repro.core import backend as BK
from repro.core.hybrid import PersiaTrainer, TrainMode
from repro.core.pipeline import PipelinedTrainer
from repro.core.spans import PREFIX, PREPARE_PHASES
from repro.data.ctr import CTRDataset
from repro.optim.optimizers import OptConfig

F, RPF, D = 2, 256, 8      # fields x rows-per-field x dim
TINY = 96                  # cache slots per table: most steps evict

CFG = ModelConfig(name="sp", arch_type="recsys", n_id_fields=F,
                  ids_per_field=4, emb_dim=D, emb_rows=F * RPF,
                  n_dense_features=4, mlp_dims=(16,), n_tasks=1)
DS = CTRDataset("sp", n_rows=F * RPF, n_fields=F, ids_per_field=4,
                n_dense=4)

PIPELINE_SPANS = [f"stage/{s}" for s in
                  ("loader", "prepare", "lookup", "dense", "put")] + \
    [f"prepare/{p}" for p in PREPARE_PHASES]
FUSED_SPANS = ["step", "prepare", "step/dispatch"]


def _batches(n, seed=0):
    it = DS.sampler(32, seed=seed)
    return [{k: jnp.asarray(v) for k, v in next(it).items()}
            for _ in range(n)]


def _engine(cache_rows):
    coll = adapters.ctr_collection(CFG, lr=5e-2, field_rows=DS.field_rows())
    coll = coll.with_backend("host_lru", cache_rows)
    ad = adapters.recsys_adapter(CFG, field_rows=DS.field_rows(),
                                 collection=coll)
    trainer = PersiaTrainer(ad, TrainMode.hybrid(3),
                            OptConfig(kind="adam", lr=5e-3))
    return PipelinedTrainer(trainer, max_inflight=1)


def _phases(pm):
    return {p: pm[f"pipeline/prepare/{p}_s"] for p in PREPARE_PHASES}


def _host_events(tdir):
    """{name: [(thread line, start_ns, end_ns)]} of the persia/* host
    events in the trace under ``tdir``."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.setdefault(e.name, []).append(
                        (line.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One tiny-cache engine: an untraced run, a traced run (with a fused
    step after it), then an empty run."""
    batches = _batches(14)
    engine = _engine(TINY)
    state = engine.init(jax.random.PRNGKey(0), batches[0])
    state, _ = engine.run(state, batches[:8])
    pm_first = engine.pipeline_metrics()
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    try:
        state, _ = engine.run(state, batches[8:12])
        pm_traced = engine.pipeline_metrics()
        state, _ = engine.trainer.step(state, batches[12])
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    state, _ = engine.run(state, [])
    return {"first": pm_first, "traced": pm_traced,
            "empty": engine.pipeline_metrics(), "events": _host_events(tdir),
            "engine": engine, "state": state, "batch": batches[13]}


def test_prepare_phases_are_reported_and_bounded_by_busy(runs):
    for key in ("first", "traced"):
        pm = runs[key]
        ph = _phases(pm)
        assert all(v >= 0.0 for v in ph.values()), ph
        assert ph["evict_d2h"] > 0.0 and ph["store"] > 0.0, ph
        assert ph["slots"] > 0.0 and ph["plan"] > 0.0 and \
            ph["fault_h2d"] > 0.0, ph
        # the phases are disjoint stretches inside the prepare stage
        assert sum(ph.values()) <= pm["pipeline/prepare/busy_s"] + 1e-3


def test_prepare_phases_reset_per_run(runs):
    pm = runs["empty"]
    assert _phases(pm) == dict.fromkeys(PREPARE_PHASES, 0.0)
    assert pm["pipeline/prepare/busy_s"] == 0.0
    assert pm["pipeline/put/lost_rows"] == 0.0


@pytest.mark.parametrize("name", PIPELINE_SPANS + FUSED_SPANS)
def test_span_on_host_clock(runs, name):
    """Every documented span is on the host plane under its exact name;
    arguments (the table) never enter the name."""
    assert PREFIX + name in runs["events"], sorted(runs["events"])
    for n in runs["events"]:
        assert n.removeprefix(PREFIX) in PIPELINE_SPANS + FUSED_SPANS, n
        assert "field" not in n and "#" not in n, n


def test_prepare_phases_nest_in_the_prepare_stage(runs):
    ev = runs["events"]
    stage = ev[PREFIX + "stage/prepare"]
    fused = ev[PREFIX + "prepare"]
    for p in PREPARE_PHASES:
        for line, s, e in ev[PREFIX + f"prepare/{p}"]:
            assert any(ln == line and s0 <= s and e <= e0
                       for ln, s0, e0 in stage + fused), (p, s, e)


def test_fused_step_spans_nest(runs):
    ev = runs["events"]
    (line, s0, e0), = ev[PREFIX + "step"]
    for name in ("prepare", "step/dispatch"):
        (ln, s, e), = ev[PREFIX + name]
        assert ln == line and s0 <= s and e <= e0


def test_named_scopes_in_the_jitted_programs(runs):
    """persia/lookup, persia/tower and persia/put label the fused step's
    ops; persia/fault the fault-in scatter."""
    trainer = runs["engine"].trainer
    state, dev_ids, _ = trainer._prepare(runs["state"], runs["batch"])
    text = jax.jit(trainer.train_step).lower(
        state, runs["batch"], dev_ids).as_text(debug_info=True)
    for scope in ("persia/lookup", "persia/tower", "persia/put"):
        assert scope in text, scope
    emb = state.emb[trainer.collection.names[0]]
    k = 4
    fault = BK._fault_apply_acc.lower(
        emb["table"], emb["slot_ids"], emb["acc"], jnp.zeros(k, jnp.int32),
        jnp.zeros((k, D), jnp.float32), jnp.zeros(k, jnp.int32),
        jnp.zeros(k, jnp.float32)).as_text(debug_info=True)
    assert "persia/fault" in fault


@pytest.mark.parametrize("cache_rows", [TINY, RPF], ids=["tiny", "roomy"])
def test_lost_put_counter(runs, cache_rows):
    """tau=3 behind a cache that recycles slots within three steps loses
    queued puts; a cache holding the whole table never does."""
    if cache_rows == TINY:
        lost = runs["first"]["pipeline/put/lost_rows"]
        assert lost > 0 and lost == int(lost)
        return
    batches = _batches(8)
    engine = _engine(cache_rows)
    engine.run(engine.init(jax.random.PRNGKey(0), batches[0]), batches)
    pm = engine.pipeline_metrics()
    assert pm["pipeline/put/lost_rows"] == 0.0
    assert _phases(pm)["evict_d2h"] == 0.0


def test_span_seconds_sum_over_tables():
    backends = {"a": BK.DenseBackend(BK.EmbeddingSpec(rows=8, dim=2)),
                "b": BK.DenseBackend(BK.EmbeddingSpec(rows=8, dim=2))}
    backends["a"].span_s["prepare/plan"] = 0.25
    backends["b"].span_s["prepare/plan"] = 0.5
    backends["b"].span_s["prepare/slots"] = 1.0
    assert BK.span_seconds(backends) == {"prepare/plan": 0.75,
                                         "prepare/slots": 1.0}
