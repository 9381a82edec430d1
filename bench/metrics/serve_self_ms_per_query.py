"""Time in ``StoreQueryEngine.serve`` that no layer's span covers
(``rstore.serve`` spans' self time: the freshness check, key lists, routing
and result assembly), summed over the window's waves, per query (ms).
Nothing to read where the program logs no spans."""
import spans


def read(run):
    return spans.per_query(run, lambda w: spans.self_ms(w, "rstore.serve"))
