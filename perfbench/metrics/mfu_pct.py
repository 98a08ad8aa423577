"""mfu_pct: model FLOPs of a round over what the cell's chips could do in
the traced round time. Needs a device trace; returns None without one."""

from perfbench.harness import arith


def read(ctx):
    if ctx.get("trace") is None or ctx.get("peaks") is None:
        return None
    seconds = ctx["trace"]["window_s"] / ctx["traced_rounds"]
    return arith.mfu_pct(ctx["model_flops_per_round"], seconds,
                         ctx["chips"], ctx["peaks"])
