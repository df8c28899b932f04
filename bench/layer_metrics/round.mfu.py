"""Whole round: model FLOP/s utilisation. The useful samples of the
traced window times the forward-and-backward operations one sample
requires (bench/flops.py), over the window's seconds, the chips and the
chip's published bf16 peak. The f32 convolutions run as one bf16 pass at
the TPU's default precision, so the bf16 peak is the ceiling. Padded
steps are computed but not required, and do not count."""


def read(ctx):
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return (100.0 * ctx["samples"] * ctx["flops_per_sample"]
            / ctx["trace"]["window_s"] / peak)
