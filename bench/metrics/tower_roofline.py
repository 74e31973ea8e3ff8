"""``tower_roofline``: the tower matmuls' share of their roofline.

The least time the chip could take for the traced steps' tower FLOPs (from
shapes) at the bf16 peak, over the summed device time of the trace's matmul
ops (convolutions, dots and the fusions into their outputs), averaged over
the chips. The tower is compute-bound at these widths: its arithmetic
intensity at batch 4096 is far above the chip's FLOP-to-byte ratio."""


def read(run):
    if run.trace is None or run.traced is None or run.traced.samples <= 0:
        return None
    flops = run.tower_flops_per_sample * run.traced.samples / run.chips
    t_min = flops / run.peaks["bf16_flops_per_s"]
    shares = []
    for d in run.trace["devices"].values():
        if d["matmul_s"] <= 0:
            return None
        shares.append(t_min / d["matmul_s"])
    return 100.0 * sum(shares) / len(shares)
