"""Mean time, in ms, of the benchmark's timer around
``IngestGateway.commit`` in the window, including the drains that its
watermarks fire.  Nothing to read without a writer."""


def read(run):
    if not run.commit_s:
        return None
    return 1e3 * sum(run.commit_s) / len(run.commit_s)
