"""The multi-process launcher (repro/launch/cluster.py): a real
trainer + k PS subprocess run over the RPC wire, and the kill-a-shard
drill — SIGKILL one shard mid-run, reshard its spooled rows onto the
survivors, keep training."""
import os

import numpy as np
import pytest

from repro.launch.cluster import run_cluster
from repro.launch.shards import parse_emb_shards, shards_for_table


def test_emb_shards_grammar_is_shared_across_launchers():
    assert parse_emb_shards(4) == 4
    assert parse_emb_shards("4") == 4
    assert parse_emb_shards(None) == 1
    assert parse_emb_shards(" field_00=4, field_02=2") == \
        {"field_00": 4, "field_02": 2}
    with pytest.raises(ValueError, match="expected 'table=k'"):
        parse_emb_shards("field_00=")
    with pytest.raises(ValueError):
        parse_emb_shards("nope")
    assert shards_for_table(4, "vocab") == 4
    assert shards_for_table({"vocab": 2}, "vocab") == 2
    assert shards_for_table({"other": 2}, "vocab") == 1


@pytest.mark.timeout(240)
def test_cluster_smoke_two_ps(tmp_path):
    res = run_cluster(steps=5, n_ps=2, workdir=str(tmp_path))
    assert res["steps"] == 5
    assert res["members"] == 2
    assert np.isfinite(res["loss"])
    assert res["steps_per_s"] > 0
    # a clean run never reshards
    assert not [e for e in res["events"] if e["kind"] == "reshard"]
    # every shard published its port and spooled applied state
    for i in range(2):
        assert os.path.isdir(tmp_path / f"ps{i}.spool")


@pytest.mark.timeout(240)
def test_cluster_kill_a_shard_reshards_onto_survivors(tmp_path):
    res = run_cluster(steps=10, n_ps=3, kill_shard=1, kill_at=4,
                      workdir=str(tmp_path))
    assert res["members"] == 2
    resh = [e for e in res["events"] if e["kind"] == "reshard"]
    assert resh and resh[0]["dead"] == [1]
    assert resh[0]["k"] == 2
    # applied puts were spooled before their ack: the kill loses at most
    # in-flight work, never applied rows
    assert res["lost_rows"] and all(v == 0
                                    for v in res["lost_rows"].values())
    assert np.isfinite(res["loss"])


# ---------------------------------------------------------------------------
# one process per chip: what the launchers hand their children and JAX
# ---------------------------------------------------------------------------

def _proc_environ(pid: int) -> dict:
    with open(f"/proc/{pid}/environ", "rb") as f:
        pairs = f.read().split(b"\0")
    return dict(p.decode().split("=", 1) for p in pairs if b"=" in p)


@pytest.mark.timeout(120)
def test_spawn_ps_child_is_pinned_to_the_cpu(tmp_path, monkeypatch):
    """A PS child never opens the accelerator its trainer parent holds:
    whatever platform the parent asks for, the child's JAX gets the CPU."""
    from repro.launch.cluster import spawn_ps
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    member = spawn_ps(str(tmp_path), 0)
    try:
        assert member.proc.poll() is None          # serving on its port
        assert _proc_environ(member.proc.pid)["JAX_PLATFORMS"] == "cpu"
    finally:
        member.proc.kill()
        member.proc.wait(timeout=30)


def test_compile_cache_dir_honours_env_else_fixed(tmp_path, monkeypatch):
    import jax

    from repro.launch import compile_cache as CC
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CC.ENV, str(tmp_path))
        assert CC.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # set nothing
        monkeypatch.delenv(CC.ENV)
        first = CC.enable_compile_cache()
        assert first == CC.enable_compile_cache()
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        assert first == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.timeout(300)
def test_importing_repro_initialises_no_backend():
    """Importing any module of the package opens no device: a launcher
    may still re-exec itself (``--tuned-host``) or hand the chip on."""
    import subprocess
    import sys
    code = (
        "import importlib, pkgutil, repro\n"
        "from jax._src import xla_bridge\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    importlib.import_module(m.name)\n"
        "    assert not xla_bridge.backends_are_initialized(), m.name\n"
        "print('NO_BACKEND')\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=280)
    assert "NO_BACKEND" in res.stdout, res.stderr[-3000:]


def test_tuned_host_refuses_once_a_backend_is_up(monkeypatch):
    import jax

    from repro.launch import hostenv
    monkeypatch.delenv(hostenv._MARKER, raising=False)
    jax.devices()                                # this process holds one
    before = dict(os.environ)
    with pytest.raises(RuntimeError, match="before JAX initialises"):
        hostenv.apply_tuned_host()
    assert dict(os.environ) == before
