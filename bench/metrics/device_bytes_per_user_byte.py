"""The chip's peak bytes in use at the end of the run over the raw bytes
of every record version the store holds.  Nothing to read where the
device reports no memory statistics."""


def read(run):
    if run.device_peak_bytes <= 0:
        return None
    return run.device_peak_bytes / run.raw_bytes
