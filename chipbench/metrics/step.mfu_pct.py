"""Model FLOP/s utilization of the window, %: the FLOPs the forward and
backward passes require (``chipbench.counts``, by the model's own count
in ``chipbench/models/<model>.py``) over the window's wall time, chips
and the chip's peak."""


def read(run):
    w = run.window
    return (100.0 * w["work"]["flops"]
            / (w["wall_s"] * run.chips * run.peaks["flops_per_s"]))
