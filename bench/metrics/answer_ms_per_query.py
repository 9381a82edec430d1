"""Time in the answer step (``rstore.answer`` spans' self time: the chunk
decoding nested in them is left to ``decode_ms_per_query``), summed over
the window's waves, per query (ms).  Nothing to read where the program logs
no spans."""
import spans


def read(run):
    return spans.per_query(run, lambda w: spans.self_ms(w, "rstore.answer"))
