"""``emb_roofline``: the embedding tier's share of its roofline.

The least time HBM needs to move the bytes the traced steps' lookups,
queue pushes and pops, and adagrad applies must move (counted from each
batch's unique ids per table, ``harness/work.py``) at the published HBM
bandwidth, over the summed device time of the trace's gather, scatter and
sort ops, averaged over the chips. These ops do no FLOPs worth counting, so
bandwidth is their roof."""


def read(run):
    if run.trace is None or run.emb_bytes_traced <= 0:
        return None
    t_min = run.emb_bytes_traced / run.chips / run.peaks["hbm_bytes_per_s"]
    shares = []
    for d in run.trace["devices"].values():
        if d["embedding_s"] <= 0:
            return None
        shares.append(t_min / d["embedding_s"])
    return 100.0 * sum(shares) / len(shares)
