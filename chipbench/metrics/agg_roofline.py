"""Share of the aggregation roofline, in %: the least time the chip needs
for the aggregations' required bytes (chipbench/models, at peak HBM
bandwidth; the adds are far below the FLOP bound, so bytes bound it), over
the aggregation ops' device time in the trace."""


def read(ctx):
    agg_s = ctx["trace"]["agg_s"]
    if agg_s <= 0 or not ctx["steps"]:
        return None
    least = ctx["work"]["agg_bytes"] / ctx["peak"].hbm_bytes_per_s
    return 100.0 * least * ctx["steps"] / agg_s
