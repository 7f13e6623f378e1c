"""Model step: the model operations of the window's steps, over the
window's seconds and the bf16 peak of every chip the cell runs on, in
percent. The operations come from the configuration's family in
``flops/``; recomputation is not counted."""


def read(ctx):
    flops = ctx["flops_per_step"] * ctx["steps"]
    peak = ctx["chips"] * ctx["peak"]["bf16_flops"]
    return 100.0 * flops / ctx["window_s"] / peak
