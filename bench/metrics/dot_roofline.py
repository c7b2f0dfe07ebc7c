"""Share of the roofline that the dot kernel reaches: the bytes its calls
must read (``counts.dot_bytes``) at the chip's HBM bandwidth, over the
kernel's device time. A dot reads 1 byte for each 4 operations, far
below the chip's ratio, so bandwidth bounds it."""

from bench import counts

#: the dot's Pallas kernel in the trace's operation line
KERNEL = r"^%dot_accumulators\b"


def read(run):
    calls = run.trace.ops_matching(KERNEL)
    if not calls:
        return None
    need = len(calls) * counts.dot_bytes(run.records["n"]) \
        / run.peak["hbm_bytes_per_s"]
    return 100.0 * need / (sum(e.dur for e in calls) * 1e-9)
