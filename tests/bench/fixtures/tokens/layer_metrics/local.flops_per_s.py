"""Local training: the operations the window's local steps require
(the task's ``work``) over the device seconds of the ``fl.local``
scope. Nothing where either is missing."""


def read(ctx):
    need = ctx["work"].get("fl.local", {}).get("flops")
    spent = ctx["scope_s"].get("fl.local", 0.0)
    if not need or spent <= 0:
        return None
    return need / spent
