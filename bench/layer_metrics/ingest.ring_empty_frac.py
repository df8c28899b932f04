"""Ingest: the share of the window's rounds that found no staged round
waiting in the staging ring when they asked for their own
(``RoundRecord.ingest_ready == 0``): rounds that waited on the staging
thread. Nothing where the program keeps no such counter."""


def read(ctx):
    vals = [getattr(r, "ingest_ready", None) for r in ctx["records"]]
    if not vals or None in vals:
        return None
    return sum(v == 0 for v in vals) / len(vals)
