"""Time in device-table multigets (``rstore.gather`` spans: the table's
upload if dirty, the index build, the jitted gather, its copy to the host
and the slicing into values), summed over the window's waves, per query
(ms).  Nothing to read where the program logs no spans."""
import spans


def read(run):
    return spans.per_query(run, lambda w: spans.total_ms(w, "rstore.gather"))
