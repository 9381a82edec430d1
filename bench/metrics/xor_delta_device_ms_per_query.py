"""Summed device time of the XOR-delta kernel's trace events in the
window, per query (ms).  Nothing to read where the trace holds none."""
import tracefold


def read(run):
    if run.events is None:
        return None
    s = tracefold.kernel_seconds(run.events, "xor_delta")
    return 1e3 * s / run.n_queries if s > 0 else None
