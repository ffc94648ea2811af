"""Device time of the feature-assembly kernels per step, ms, mean over
chips: the Pallas custom calls of ``kernels/assemble`` (the cache search
and the select pass), whose HLO names carry ``assemble``."""

PATTERN = r"^%[^ ]*assemble[^ ]* = .*custom-call"


def read(run):
    if run.trace is None:
        return None
    per_chip = run.trace.op_seconds(PATTERN)
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / run.window["steps"]
