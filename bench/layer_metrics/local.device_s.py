"""Local training: the device seconds of the round program's
``fl.local`` scope per round, the union of the intervals of its ops
(the local scan's ``while`` and every op inside it) over the traced
window, divided by the window's rounds. Nothing where the trace finds
no op of the scope."""


def read(ctx):
    spent = ctx["scope_s"].get("fl.local", 0.0)
    if spent <= 0:
        return None
    return spent / len(ctx["records"])
