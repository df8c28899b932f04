"""Ingest: the megabytes (10**6 bytes) placed on the device for a round,
mean over the window's rounds of ``RoundRecord.ingest_placed_bytes``:
the staged batches (pixels, or sample indices where the round gathers
from a device table), masks and ids. Nothing where the program records
no such counter."""


def read(ctx):
    vals = [getattr(r, "ingest_placed_bytes", None) for r in ctx["records"]]
    if not vals or None in vals or not any(vals):
        return None
    return sum(vals) / len(vals) / 1e6
