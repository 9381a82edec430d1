"""Time decoding fetched chunks and chunk maps (``rstore.decode`` spans:
zlib and XOR delta), summed over the window's waves, per query (ms).
Nothing to read where the program logs no spans."""
import spans


def read(run):
    return spans.per_query(run, lambda w: spans.total_ms(w, "rstore.decode"))
