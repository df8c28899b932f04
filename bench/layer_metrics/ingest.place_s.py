"""Ingest: the blocking host-to-device copy of a round's staged cohort,
the mean duration of the program's ``fl.ingest.place`` span on
whichever thread places, as ``RoundRecord.ingest_place_seconds``
carries it. Nothing where the program records no such span."""


def read(ctx):
    vals = [getattr(r, "ingest_place_seconds", None) for r in ctx["records"]]
    if not vals or None in vals or not any(vals):
        return None
    return sum(vals) / len(vals)
