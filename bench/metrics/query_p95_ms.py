"""95th percentile latency, in ms, over every query due in the window
(each query's own latency, not a statistic of waves)."""
import openloop


def read(run):
    return 1e3 * openloop.percentile(run.window.latency_s, 95)
