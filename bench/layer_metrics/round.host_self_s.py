"""Round driver: the host's own time in a round, the mean self time of
the program's ``fl.round`` span: its duration less the wait on the
staging ring (``fl.ingest.wait``) and the wait for the device's results
(``fl.round.sync``), from ``RoundRecord.seconds``,
``ingest_host_seconds`` and ``sync_seconds``. Nothing where the program
records no sync span."""


def read(ctx):
    recs = ctx["records"]
    if not recs or any(getattr(r, "sync_seconds", None) is None
                       for r in recs):
        return None
    return sum(r.seconds - r.ingest_host_seconds - r.sync_seconds
               for r in recs) / len(recs)
