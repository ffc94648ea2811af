"""Host staging wall, hidden and exposed, ms per window epoch: the
runner's ``stage_time_s`` over the window (schedule views, C_sec,
collation and pull plans of each staged epoch)."""


def read(run):
    w = run.window
    return 1e3 * w["stage_s"] / w["epochs"]
