"""Versions the writer committed in the window that were durable when it
closed, over the window's length.  Nothing to read without a writer."""


def read(run):
    if not run.writer:
        return None
    return run.window.durable_at_close / run.window.close_s
