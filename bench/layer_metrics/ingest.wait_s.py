"""Ingest: the mean wait of a window's round on the staging ring and on
host-to-device placement, as ``RoundRecord`` times them."""


def read(ctx):
    recs = ctx["records"]
    return sum(r.ingest_host_seconds + r.ingest_device_seconds
               for r in recs) / len(recs)
