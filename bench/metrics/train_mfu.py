"""train_mfu: model FLOPs per sample × the window's samples/s, over the
chips' bf16 peak (the chip publishes no f32 peak)."""


def read(ctx):
    peak = ctx["peaks"]["flops_bf16_per_s"] * ctx["chips"]
    return 100.0 * ctx["model_flops_per_sample"] * ctx["samples_per_s"] / peak
