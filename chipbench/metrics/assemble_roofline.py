"""The assembly kernels' share of their roofline, %: the least time the
bytes assembly needs take at the chip's HBM bandwidth, over the kernels'
device time. The bytes are every valid input row read once and written
once in float32 plus its int32 id (``chipbench.counts``), so a kernel
that replaces this one is held to the same work. Bandwidth bounds it:
assembly does no arithmetic."""

PATTERN = r"^%[^ ]*assemble[^ ]* = .*custom-call"


def read(run):
    if run.trace is None:
        return None
    seconds = sum(run.trace.op_seconds(PATTERN))
    if seconds <= 0:
        return None
    least = run.window["work"]["assemble_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
