"""Median latency, in ms, of every query due in the window: from when it
was due until its wave returned."""
import openloop


def read(run):
    return 1e3 * openloop.percentile(run.window.latency_s, 50)
