"""Bytes of slot rows the device-table gathers fetched only to pad an index
up to its power-of-two length (``pad_bytes`` of the ``rstore.gather``
spans), summed over the window's waves, per query (B).  Nothing to read
where the program logs no spans, or its gathers count no padding."""
import spans


def pad_bytes(wave) -> int:
    return sum(s.counts.get("pad_bytes", 0) for s in wave
               if s.name == "rstore.gather")


def read(run):
    waves = spans.window_waves(run) or []
    if not any("pad_bytes" in s.counts for w in waves for s in w):
        return None
    return spans.per_query(run, pad_bytes)
