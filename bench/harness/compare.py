"""The comparison that decides ``correct``.

Three numbers, each with its limit from the configuration:

* ``loss_gap``: the largest relative gap between the program's loss and the
  reference's over the compared steps;
* ``grad1_gap``: the worst leaf's gap between the norms of the first
  gradient as each optimizer received it (the tower's as Adam took it, each
  table's put as it entered the staleness queue);
* ``change_gap``: the worst leaf's gap between the norms of the parameters'
  change over the compared steps (tower leaves; each table's touched rows;
  and, of a host-backed table, the touched rows its device cache no longer
  holds, read from its host store: what the write-back stored).

A leaf's gap is ``|norm_program - norm_reference|`` over the larger of the
reference's norm of that leaf and of the median leaf. Leaves whose reference
gradient is under a thousandth of the median leaf's move by round-off alone
and are left out; a table's stored rows count with the table.
"""
from __future__ import annotations

import numpy as np

NAMES = ("loss_gap", "grad1_gap", "change_gap")
NOUGHT = 1e-3


def _worst(prog: dict, ref: dict, counted: set) -> tuple[float, str]:
    med = float(np.median([ref[k] for k in counted]))
    worst, at = 0.0, ""
    for k in sorted(counted):
        if k not in prog:
            return float("inf"), k
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not np.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, at = g, k
    return worst, at


def _base(leaf: str) -> str:
    """The leaf whose gradient decides whether ``leaf`` counts: a table's
    stored rows (``store/<table>``) go with the table (``emb/<table>``)."""
    return "emb/" + leaf[len("store/"):] if leaf.startswith("store/") \
        else leaf


def gaps(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: {"losses": [...], "grad1": {leaf: norm},
    "change": {leaf: norm}} -> {name: value, name + "_at": leaf}."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss = float("inf")
    else:
        loss = float(np.max(np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30)))
    g_med = float(np.median(list(ref["grad1"].values())))
    counted = {k for k, v in ref["grad1"].items() if v >= NOUGHT * g_med}
    g1, g1_at = _worst(prog["grad1"], ref["grad1"], counted)
    ch, ch_at = _worst(prog["change"], ref["change"],
                       {k for k in ref["change"] if _base(k) in counted})
    return {"loss_gap": loss, "grad1_gap": g1, "grad1_gap_at": g1_at,
            "change_gap": ch, "change_gap_at": ch_at,
            "leaves_counted": len(counted),
            "leaves_left_out": sorted(set(ref["grad1"]) - counted)}


def verdict(g: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}). A number without a limit
    fails: an uncalibrated comparison proves nothing."""
    checks = {}
    ok = True
    for n in NAMES:
        lim = limits.get(n)
        checks[n] = {"value": g[n], "limit": lim}
        if lim is None or not (g[n] <= lim):
            ok = False
    return ok, checks
