"""mfu.decode: the whole decode step's roofline share, in percent: the
least time the chip could take for the step's essential operations and
bytes (``bench.work``), the larger of the two bounds, over the measured
time per step.  A plain operation share would be about 1% in decode and
bound nothing, so the bytes bound is the one that speaks here."""

from bench.work import roofline_seconds


def read(r):
    if r["kind"] != "decode":
        return None
    t_min, _ = roofline_seconds(r["flops_per_step"], r["bytes_per_step"], r["peaks"])
    return 100.0 * t_min * r["steps"] / (r["window_s"] * r["chips"])
