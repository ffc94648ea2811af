"""Chip idle between epochs, ms per window epoch, mean over chips: the
idle time that falls inside the epoch loop's ``rapidgnn.epoch.*`` host
spans (dispatch, loss read-back, report). Only the main thread records
these spans. The program before them has none, so this returns None."""

from chipbench import trace

PREFIX = "rapidgnn.epoch."


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    spans = [h for h in run.trace.host if h[0].startswith(PREFIX)]
    if not spans:
        return None
    idle = [sum(max(0.0, min(ge, e) - max(gs, s))
                for gs, ge, _ in trace.gaps(c, run.trace.lo, run.trace.hi)
                for _, s, e in spans)
            for c in run.trace.chips]
    return 1e-6 * sum(idle) / len(idle) / run.window["epochs"]
