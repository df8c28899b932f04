"""Local training: the share of the client-steps the round program
computes that train on data, from the program's own counters: the sum of
``RoundRecord.steps_useful`` (unmasked slots of the staged mask) over
the sum of ``RoundRecord.steps_run`` (client-steps the local scan
computes). Nothing where the program records no ``steps_run``."""


def read(ctx):
    recs = ctx["records"]
    runs = [getattr(r, "steps_run", None) for r in recs]
    if not recs or None in runs or not sum(runs):
        return None
    return sum(r.steps_useful for r in recs) / sum(runs)
