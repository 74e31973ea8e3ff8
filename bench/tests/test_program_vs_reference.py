"""The program against the plain reference on the CPU, at a small size, on
each path a cell drives: the fused dense step, and the pipelined host_lru
trainer with a cache smaller than the rows its compared steps touch."""
import jax
import numpy as np
import pytest

from bench import run as R
from bench.harness import compare, program, reference, work

from .conftest import CPU_LIMITS, CPU_PEAKS, fixture_cell, small_cell

SEED = 2**31 + 101
# the compared steps touch about 750 rows per table: the last four steps'
# rows fit this cache, the twelve steps' do not
CACHE_ROWS = 700
# DLRM-DCNv2's fields, its bags of 1 to 100 ids each full
DCN = ("dlrm-dcnv2", "zipf1.2-b4096-fixed-bags")


def test_fused_dense_matches_reference():
    cell = small_cell("criteo-dense-zipf", limits=CPU_LIMITS)
    out = R.execute(cell, SEED, 0.5, False, jax.devices()[:1], CPU_PEAKS)
    assert out["correct"], out["checks"]
    assert out["window"]["steps"] > 0
    assert out["window"]["compiles"] == 0
    assert out["compared"]["leaves_left_out"] == []
    assert out["compared"]["stored_rows"] == 0


def test_host_lru_fault_in_and_write_back_match_reference():
    """The compared steps, at tau = 3, overflow the device cache: rows are
    faulted in, the least recent are evicted and written back to the host
    store once their queued puts have been applied, and both the tables and
    the rows read back from the host store follow the reference."""
    cell = small_cell("kwai-hostlru-zipf", rows=5000, cache_rows=CACHE_ROWS,
                      batch=64, limits=CPU_LIMITS)
    cfg = cell.config
    assert cfg["tables"]["staleness"] == 3
    batches = cell.batches(SEED, 0, cfg["check"]["steps"])
    system = program.System(cfg, 64)
    system.init(SEED, batches[0])
    prog = R.compared_steps(system, batches, cfg["model"]["n_id_fields"])
    c = system.counters()
    assert c["writebacks"] > 0 and c["faults"] > c["writebacks"]
    stored = {k: len(x) for k, (_, x) in prog["subsets"].items()}
    assert len(stored) == cfg["model"]["n_id_fields"]
    assert min(stored.values()) > 0
    system.free()
    ref = reference.Reference(cfg, SEED, cell.tower).run(
        batches, prog["subsets"])
    g = compare.gaps(prog, ref)
    assert all(ref["change"][k] > 0 for k in prog["subsets"])
    ok, checks = compare.verdict(g, cfg["check"]["limits"])
    assert ok, checks


def test_pipelined_host_lru_run_matches_reference():
    cell = small_cell("kwai-hostlru-zipf", rows=5000, cache_rows=CACHE_ROWS,
                      batch=64, limits=CPU_LIMITS)
    out = R.execute(cell, SEED, 0.5, False, jax.devices()[:1], CPU_PEAKS)
    assert out["correct"], out["checks"]
    assert out["window"]["steps"] > 0
    assert out["compared"]["stored_rows"] > 0


def test_reference_subset_is_the_rows_change():
    """A subset leaf of the reference is the change of just those rows."""
    cell = small_cell("kwai-hostlru-zipf", rows=5000, batch=64)
    cfg = cell.config
    batches = cell.batches(SEED, 0, 4)
    ids = reference.touched(batches, cfg["model"]["n_id_fields"])
    every = ids[1]
    half = every[::2]
    ref = reference.Reference(cfg, SEED, cell.tower).run(
        batches, {"all": (1, every), "half": (1, half), "none": (1, half[:0])})
    assert np.isclose(ref["change"]["all"], ref["change"]["emb/field_01"])
    assert 0 < ref["change"]["half"] < ref["change"]["all"]
    assert ref["change"]["none"] == 0.0


# Read at the harness's commit before per-field tables, the tower by name
# and the mesh path came in (CPU): what the program and the reference give
# on a tiny preset of each cell, the three gaps, and the embedding bytes
# counted over the steps. The harness reads the same to the last digit.
BEFORE = {
    "criteo-dense-zipf": {
        "prog_losses": [0.743215799331665, 0.7137912511825562,
                        0.7294710278511047, 0.7409405708312988,
                        0.6928321719169617],
        "ref_losses": [0.743215799331665, 0.7137912511825562,
                       0.72947096824646, 0.7409405708312988,
                       0.6928321719169617],
        "gaps": [8.170941321855667e-08, 2.954642956069691e-07,
                 2.5698793918366205e-08],
        "emb_bytes": 1453976.0},
    "kwai-hostlru-zipf": {
        "prog_losses": [0.7224737405776978, 0.7194555401802063,
                        0.7092657685279846, 0.7092567682266235,
                        0.6528815627098083, 0.6645671129226685,
                        0.6977107524871826, 0.6565734148025513,
                        0.6494482159614563, 0.6461275815963745,
                        0.6469232439994812, 0.6523492336273193],
        "ref_losses": [0.722473680973053, 0.7194555401802063,
                       0.7092657685279846, 0.7092567682266235,
                       0.6528816223144531, 0.6645671129226685,
                       0.6977107524871826, 0.6565734148025513,
                       0.6494482159614563, 0.6461275815963745,
                       0.6469232439994812, 0.6523492336273193],
        "gaps": [9.129471980554955e-08, 1.3369953950871704e-07,
                 9.722597343306518e-09],
        "emb_bytes": 8849624.0},
}


@pytest.mark.parametrize("name,kw", [
    ("criteo-dense-zipf", {}),
    ("kwai-hostlru-zipf", {"rows": 5000, "cache_rows": CACHE_ROWS,
                           "batch": 64})])
def test_reads_as_before(name, kw):
    cell = small_cell(name, **kw)
    cfg = cell.config
    K = cfg["check"]["steps"]
    F = cfg["model"]["n_id_fields"]
    batches = cell.batches(SEED, 0, K)
    system = program.System(cfg, cell.batch)
    system.init(SEED, batches[0])
    prog = R.compared_steps(system, batches, F)
    system.free()
    ref = reference.Reference(cfg, SEED, cell.tower).run(batches,
                                                         prog["subsets"])
    g = compare.gaps(prog, ref)
    u = [work.unique_counts(b["ids"], F) for b in batches]
    got = {"prog_losses": prog["losses"], "ref_losses": ref["losses"],
           "gaps": [g[n] for n in compare.NAMES],
           "emb_bytes": sum(work.emb_bytes(u[i], u[i - 3],
                                           cfg["model"]["emb_dim"])
                            for i in range(3, K))}
    assert got == BEFORE[name]


def test_reference_reads_per_field_id_lists():
    """The reference follows the same steps whether a batch holds its ids
    as one padded ``(B, F, L)`` array or as a list of per-field
    ``(B, L_f)`` arrays, each cut to its own widest bag."""
    cell = small_cell("criteo-dense-zipf", batch=64)
    cfg = cell.config
    batches = cell.batches(SEED, 0, 3)
    widths = [1, 2, 2]
    ragged = [dict(b, ids=[b["ids"][:, f, :w] for f, w in enumerate(widths)])
              for b in batches]
    for b in batches:        # the cut drops only padding
        b["ids"][:, 0, 1:] = -1
    padded = reference.Reference(cfg, SEED, cell.tower).run(batches)
    ragged = reference.Reference(cfg, SEED, cell.tower).run(ragged)
    assert [len(x) for x in ragged["touched"]] == \
        [len(x) for x in padded["touched"]]
    np.testing.assert_allclose(ragged["losses"], padded["losses"],
                               rtol=1e-6)
    for k, v in padded["change"].items():
        np.testing.assert_allclose(ragged["change"][k], v, rtol=1e-5)


def one_width(ids_per_field: list) -> list:
    """Every field's touched ids padded to the widest field's power of two,
    as the harness padded them before each field had a width of its own."""
    n = max(x.size for x in ids_per_field)
    width = 1 << max(int(n - 1).bit_length(), 10)
    return [np.concatenate([x, np.full(width - x.size, -1, np.int64)])
            for x in ids_per_field]


def test_padded_gives_each_field_its_own_width():
    """Each field's touched ids, in order, then -1 up to the least power of
    two that holds them, and never under 1,024."""
    cell = fixture_cell(*DCN, batch=128, rows=20000)
    ids = reference.touched(cell.batches(SEED, 0, 5), 26)
    got = reference.padded(ids)
    for x, p in zip(ids, got):
        w = p.size
        assert w & (w - 1) == 0 and w >= max(x.size, 1024)
        assert w == 1024 or w < 2 * x.size
        np.testing.assert_array_equal(p[:x.size], x)
        assert np.all(p[x.size:] == -1)
    sizes = [p.size for p in got]
    assert min(sizes) == 1024 and max(sizes) >= 8192
    assert sizes[20] == max(sizes)          # the field of 100-id bags


def test_per_field_widths_read_as_one_width(monkeypatch):
    """The reference's losses, first gradients and changes at each field's
    own width are those at one width for every field: the padding rows are
    drawn, never touched and never compared."""
    cell = fixture_cell(*DCN, batch=128, rows=20000)
    cfg = cell.config
    batches = cell.batches(SEED, 0, cfg["check"]["steps"])
    own = reference.Reference(cfg, SEED, cell.tower).run(batches)
    monkeypatch.setattr(reference, "padded", one_width)
    one = reference.Reference(cfg, SEED, cell.tower).run(batches)
    np.testing.assert_allclose(own["losses"], one["losses"], rtol=1e-6)
    for k in ("grad1", "change"):
        assert own[k].keys() == one[k].keys()
        for leaf, v in one[k].items():
            np.testing.assert_allclose(own[k][leaf], v, rtol=1e-6,
                                       err_msg=f"{k} {leaf}")


def test_compared_steps_read_each_field_at_its_own_width():
    """The program's rows are read at each field's own width, before and
    after the compared steps and from a host store, and follow the
    reference: DLRM-DCNv2's fields of 1, 100, 8 and 12 ids, every bag full,
    the 8-id field host_lru behind a cache smaller than its touched rows."""
    cell = fixture_cell(*DCN, batch=64, rows=20000, fields=[5, 20, 11, 19],
                        mix_keys={"layout": "padded"}, limits=CPU_LIMITS)
    cfg = cell.config
    # eight steps touch ~850 rows of the 8-id field, any four in a row
    # (the steps a put spends in the tau = 3 queue) under 570
    cfg["fields"][2].update(rows=5000, backend="host_lru", cache_rows=640)
    cfg["check"]["steps"] = 8
    batches = cell.batches(SEED, 0, 8)
    system = program.System(cfg, cell.batch)
    system.init(SEED, batches[0])
    widths, read = [], system.rows
    system.rows = lambda ids: widths.append(
        {n: len(x) for n, x in ids.items()}) or read(ids)
    prog = R.compared_steps(system, batches, 4)
    system.free()
    assert len(widths) == 2 and widths[0] == widths[1]
    assert sorted(set(widths[0].values())) == [1024, 2048, 8192]
    assert len(prog["subsets"]["store/field_02"][1]) > 0
    ref = reference.Reference(cfg, SEED, cell.tower).run(batches,
                                                         prog["subsets"])
    ok, checks = compare.verdict(compare.gaps(prog, ref),
                                 cfg["check"]["limits"])
    assert ok, checks
