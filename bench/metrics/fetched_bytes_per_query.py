"""Padded device-table rows gathered in the window (``KVSStats
.bytes_fetched`` of every table), per query (bytes)."""


def read(run):
    return run.fetched_bytes / run.n_queries
