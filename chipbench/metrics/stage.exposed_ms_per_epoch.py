"""Host staging left on the critical path, ms per window epoch: the
runner's ``exposed_stage_s`` (the wait for the background stage after
the device epoch ended) plus the window call's synchronous first stage."""


def read(run):
    w = run.window
    return 1e3 * w["exposed_stage_s"] / w["epochs"]
