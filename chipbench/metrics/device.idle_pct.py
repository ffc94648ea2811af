"""Share of the traced window in which no op ran on the chip, %, mean
over chips (each chip's share is logged on its own line)."""


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    idle = run.trace.idle_share()
    return 100.0 * sum(idle) / len(idle)
