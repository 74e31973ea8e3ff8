"""Multi-device correctness of every shard_map path, run in a subprocess
with 8 forced host devices (the main test process keeps 1 device).

Checks sharded == single-device oracle for: embedding PS lookup/put (both
modes), MoE expert parallelism, and the distributed decode attention.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    from repro.core import embedding_ps as PS
    from repro.models.moe import moe_init, moe_forward
    from repro.configs.base import ModelConfig, BlockCfg

    # ---- embedding PS: model mode ----------------------------------------
    spec = PS.EmbeddingSpec(rows=64, dim=16, mode="model", optimizer="sgd",
                            lr=0.5)
    st = PS.ps_init(jax.random.PRNGKey(0), spec, n_shards=4)
    ids = jnp.asarray(np.random.default_rng(0).integers(-1, 64, (8, 6)),
                      jnp.int32)
    local = PS.lookup(st, spec, ids)                 # no-mesh oracle
    g = jnp.asarray(np.random.default_rng(1)
                    .standard_normal((48, 16)).astype(np.float32))
    st_after_local = PS.apply_put(st, spec, ids.reshape(-1), g)
    with jax.sharding.set_mesh(mesh):
        st_sh = jax.device_put(st, {"table": NamedSharding(mesh, P("model", None))}["table"]) \
            if False else jax.tree.map(lambda x: x, st)
        out = jax.jit(lambda s, i: PS.lookup(s, spec, i))(st, ids)
        st2 = jax.jit(lambda s, i, gg: PS.apply_put(s, spec, i, gg))(
            st, ids.reshape(-1), g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(local), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st2["table"]),
                               np.asarray(st_after_local["table"]), atol=1e-4)
    print("PS model-mode OK")

    # ---- embedding PS: full mode ------------------------------------------
    spec_f = PS.EmbeddingSpec(rows=128, dim=8, mode="full",
                              optimizer="adagrad", lr=0.3)
    stf = PS.ps_init(jax.random.PRNGKey(1), spec_f, n_shards=8)
    idsf = jnp.asarray(np.random.default_rng(2).integers(-1, 128, (16, 4)),
                       jnp.int32)
    gf = jnp.asarray(np.random.default_rng(3)
                     .standard_normal((64, 8)).astype(np.float32))
    local_out = PS.lookup(stf, spec_f, idsf)
    local_put = PS.apply_put(stf, spec_f, idsf.reshape(-1), gf)
    with jax.sharding.set_mesh(mesh):
        outf = jax.jit(lambda s, i: PS.lookup(s, spec_f, i))(stf, idsf)
        stf2 = jax.jit(lambda s, i, gg: PS.apply_put(s, spec_f, i, gg))(
            stf, idsf.reshape(-1), gf)
    np.testing.assert_allclose(np.asarray(outf), np.asarray(local_out),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(stf2["table"]),
                               np.asarray(local_put["table"]), atol=1e-4)
    print("PS full-mode OK")

    # ---- MoE expert parallelism --------------------------------------------
    cfg = ModelConfig(name="m", d_model=32, d_ff=64, n_experts=8,
                      moe_top_k=2, moe_d_ff=64, n_shared_experts=1,
                      capacity_factor=8.0)
    p = moe_init(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 32))
    out_local, aux_local = moe_forward(p, cfg, x)
    with jax.sharding.set_mesh(mesh):
        out_sh, aux_sh = jax.jit(lambda p_, x_: moe_forward(p_, cfg, x_))(p, x)
    np.testing.assert_allclose(np.asarray(out_sh), np.asarray(out_local),
                               atol=2e-5)
    # balance loss is a nonlinear per-shard statistic pmean'd over shards —
    # close to, but not bit-equal with, the global statistic
    np.testing.assert_allclose(float(aux_sh["moe_balance"]),
                               float(aux_local["moe_balance"]), atol=0.05)
    print("MoE OK")

    # ---- MoE all-to-all dispatch == psum dispatch == local -------------------
    import repro.models.moe as MOE
    with jax.sharding.set_mesh(mesh):
        MOE.MOE_DISPATCH = "a2a"
        out_a2a, _ = jax.jit(lambda p_, x_: moe_forward(p_, cfg, x_))(p, x)
        MOE.MOE_DISPATCH = "psum"
    np.testing.assert_allclose(np.asarray(out_a2a), np.asarray(out_local),
                               atol=2e-5)
    ga = jax.jit(jax.grad(
        lambda p_, x_: jnp.sum(moe_forward(p_, cfg, x_)[0] ** 2)))(p, x)
    assert all(bool(jnp.isfinite(t).all()) for t in jax.tree.leaves(ga))
    print("MoE a2a OK")

    # ---- distributed decode attention ---------------------------------------
    from repro.models import layers as L
    cfg_a = ModelConfig(name="a", d_model=64, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=128, vocab_size=64)
    pa = L.gqa_init(jax.random.PRNGKey(4), cfg_a, jnp.float32)
    B, CAP = 4, 32
    cache = L.gqa_cache_init(cfg_a, B, CAP, jnp.float32)
    # pre-fill 7 tokens via local decode (no mesh)
    xs = jax.random.normal(jax.random.PRNGKey(5), (B, 8, 64)) * 0.5
    c_local = cache
    for t in range(8):
        o_local, c_local = L.gqa_decode(pa, cfg_a, xs[:, t:t+1], c_local)
    # same under the mesh (seq-sharded dist path; CAP=32 divisible by 4)
    with jax.sharding.set_mesh(mesh):
        c_sh = cache
        step = jax.jit(lambda p_, x_, c_: L.gqa_decode(p_, cfg_a, x_, c_))
        for t in range(8):
            o_sh, c_sh = step(pa, xs[:, t:t+1], c_sh)
    np.testing.assert_allclose(np.asarray(o_sh), np.asarray(o_local),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(c_sh["len"]),
                               np.asarray(c_local["len"]))
    print("dist decode OK")
    print("ALL_OK")
""")


def _run_dist_script(tmp_path, script_text, ok_marker):
    script = tmp_path / "dist_check.py"
    script.write_text(script_text)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=560)
    assert ok_marker in res.stdout, res.stdout + "\n" + res.stderr[-3000:]


@pytest.mark.timeout(600)
def test_sharded_paths_match_single_device(tmp_path):
    _run_dist_script(tmp_path, SCRIPT, "ALL_OK")


# ---------------------------------------------------------------------------
# dense init under a mesh: rows drawn on the host, spread over every device,
# the same model as one device even where the rows do not divide evenly
# ---------------------------------------------------------------------------

SCRIPT_MESH_INIT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import backend as BK, embedding_ps as PS

    spec = PS.EmbeddingSpec(rows=20_769, dim=8, mode="full")
    one_bk = BK.create_backend(spec)
    one = one_bk.init(jax.random.PRNGKey(0))
    assert one["table"].committed is False      # on the default device
    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with jax.sharding.set_mesh(mesh):
        # the trainer's keys come out of split() replicated over the mesh
        key = jax.random.split(jax.random.PRNGKey(0))[0]
        assert PS.ps_init_on_host(key, spec)["table"].shape == (20_769, 8)
        bk = BK.create_backend(spec)
        st = bk.init(jax.random.PRNGKey(0))
        ids = jnp.arange(spec.rows, dtype=jnp.int32)
        rows = np.asarray(bk.lookup(st, ids)[0])
    assert st["table"].shape[0] == 20_772
    per = {}
    for s in st["table"].addressable_shards:
        per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    assert sorted(per) == [0, 1, 2, 3] and len(set(per.values())) == 1, per
    ids = jnp.arange(spec.rows, dtype=jnp.int32)
    np.testing.assert_array_equal(
        rows, np.asarray(one_bk.lookup(one, ids)[0]))
    # and a mesh checkpoint restores row-exactly on one device
    back = one_bk.restore_from_checkpoint(jax.tree.map(np.asarray, st))
    np.testing.assert_array_equal(np.asarray(back["table"]),
                                  np.asarray(one["table"]))
    print("MESH_INIT_OK")
""")


@pytest.mark.timeout(600)
def test_dense_init_spreads_rows_over_the_mesh(tmp_path):
    _run_dist_script(tmp_path, SCRIPT_MESH_INIT, "MESH_INIT_OK")


# ---------------------------------------------------------------------------
# pipeline under a mesh: the threaded engine must match the serial trainer
# when both run with 8 forced host devices and an active global mesh
# ---------------------------------------------------------------------------

SCRIPT_PIPELINE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    from repro.configs.base import ModelConfig
    from repro.core import adapters
    from repro.core.hybrid import PersiaTrainer, TrainMode
    from repro.core.pipeline import PipelinedTrainer
    from repro.data.ctr import CTRDataset
    from repro.optim.optimizers import OptConfig

    CFG = ModelConfig(name="pm", arch_type="recsys", n_id_fields=3,
                      ids_per_field=2, emb_dim=8, emb_rows=192,
                      n_dense_features=4, mlp_dims=(16,), n_tasks=1)
    DS = CTRDataset("pm", n_rows=192, n_fields=3, ids_per_field=2, n_dense=4)
    it = DS.sampler(32)
    batches = [{k: jnp.asarray(v) for k, v in next(it).items()}
               for _ in range(8)]

    def make():
        ad = adapters.recsys_adapter(CFG, lr=5e-2,
                                     field_rows=DS.field_rows())
        return PersiaTrainer(ad, TrainMode.hybrid(2),
                             OptConfig(kind="adam", lr=5e-3))

    with jax.sharding.set_mesh(mesh):
        ta = make()
        sa = ta.init(jax.random.PRNGKey(0), batches[0])
        sa, ms_a = ta.run(sa, batches)
        tb = make()
        engine = PipelinedTrainer(tb, max_inflight=1)
        sb, ms_b = engine.run(tb.init(jax.random.PRNGKey(0), batches[0]),
                              batches)
        # a deeper pipeline must also run to completion under the mesh
        tc = make()
        deep = PipelinedTrainer(tc, max_inflight=3)
        sc, ms_c = deep.run(tc.init(jax.random.PRNGKey(0), batches[0]),
                            batches)
    assert len(ms_b) == len(ms_a) == len(ms_c) == 8
    for n in sa.emb:
        np.testing.assert_allclose(np.asarray(sa.emb[n]["table"]),
                                   np.asarray(sb.emb[n]["table"]),
                                   atol=1e-5, err_msg=n)
    for a, b in zip(jax.tree.leaves(sa.dense), jax.tree.leaves(sb.dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert all(np.isfinite(float(m["loss"])) for m in ms_c)
    assert deep.applied_order == list(range(8))
    print("PIPE_MESH_OK")
""")


@pytest.mark.timeout(600)
def test_pipeline_under_mesh_matches_serial(tmp_path):
    """The pipelined engine's worker threads dispatch against the same
    global mesh the serial facade sees: max_inflight=1 parity and a deep
    in-order run, both with 8 forced host devices."""
    _run_dist_script(tmp_path, SCRIPT_PIPELINE, "PIPE_MESH_OK")
