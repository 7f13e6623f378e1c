"""Model step: the model operations of the window's steps, over the
window's seconds and the chip's bf16 peak, in percent. The operations come
from the configuration's family in ``flops/``; recomputation is not
counted."""


def read(ctx):
    flops = ctx["flops_per_step"] * ctx["steps"]
    return 100.0 * flops / ctx["window_s"] / ctx["peak"]["bf16_flops"]
