"""Ingest: the staging thread's busy time per round, the mean duration
of the program's ``fl.ingest.stage`` span (sample, read, stack and,
where the staging thread places, the host-to-device copy), as
``RoundRecord.ingest_stage_seconds`` carries it. Nothing where the
program records no such span."""


def read(ctx):
    vals = [getattr(r, "ingest_stage_seconds", None) for r in ctx["records"]]
    if not vals or None in vals or not any(vals):
        return None
    return sum(vals) / len(vals)
