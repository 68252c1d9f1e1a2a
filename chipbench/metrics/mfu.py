"""Model FLOP/s utilization of the whole training step: the FLOPs one step
requires (chipbench/models), times the steps of the traced window, over
the window's host-clock seconds and the chips' bf16 peak, in %."""


def read(ctx):
    if not ctx["steps"]:
        return None
    flops = ctx["work"]["flops"] * ctx["steps"]
    chips = ctx["trace"]["devices"]
    return 100.0 * flops / ctx["window_s"] / (chips * ctx["peak"].flops_bf16)
