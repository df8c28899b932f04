"""Local training: the share of the local steps the fused round
computes that train on data; the rest are padding of the shape bucket,
computed and then masked away."""


def read(ctx):
    return ctx["useful_steps"] / ctx["computed_steps"]
