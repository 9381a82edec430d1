"""Time in the planner (``rstore.plan`` spans: plan IR, register-file
staging and the bitmap-VM launch), summed over the window's waves, per
query (ms).  Nothing to read where the program logs no spans."""
import spans


def read(run):
    return spans.per_query(run, lambda w: spans.total_ms(w, "rstore.plan"))
