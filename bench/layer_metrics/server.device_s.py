"""Server step: the device seconds of the round program's ``fl.server``
scope per round (the reduction pass and the epilogue), the union of
the intervals of its ops over the traced window, divided by the
window's rounds. Nothing where the trace finds no op of the scope."""


def read(ctx):
    spent = ctx["scope_s"].get("fl.server", 0.0)
    if spent <= 0:
        return None
    return spent / len(ctx["records"])
