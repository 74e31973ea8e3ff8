"""``step_mfu``: the whole step's share of the chips' bf16 peak.

Tower FLOPs per sample (forward and backward, counted from shapes) times
the window's samples per second, over chips times the published peak. The
embedding tier's gathers and scatters do no FLOPs by this count, so every
kernel's roofline share is bounded by this one's work."""


def read(run):
    w = run.window
    if w is None or w.seconds <= 0 or w.samples <= 0:
        return None
    rate = run.tower_flops_per_sample * w.samples / w.seconds
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])
