"""Time in the device-table multigets whose gather met its index length
for the first time in the process, so compiled it (``rstore.gather`` spans
with ``new_length`` 1), summed over the window's waves, per query (ms).
Nothing to read where the program logs no spans."""
import spans


def read(run):
    return spans.per_query(run, lambda w: spans.total_ms(
        w, "rstore.gather", new_length=1))
