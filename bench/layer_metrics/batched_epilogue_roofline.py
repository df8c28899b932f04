"""Server step: the ``batched_epilogue`` kernel's share of its roofline.

The least time the epilogue can take is the bytes it must move (from
shapes: bench/flops.epilogue_bytes) over the chip's published HBM
bandwidth; the kernel's time is the summed device time of its events
in the traced window, per round. The epilogue does a few operations per
byte, so bandwidth bounds it. Nothing is returned where the round took
no such kernel."""


def read(ctx):
    need = ctx["epilogue_bytes"]
    spent = ctx["trace"]["kernel_s"].get("batched_epilogue", 0.0)
    if not need or spent <= 0:
        return None
    per_round = spent / len(ctx["records"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / per_round
