"""Device milliseconds per step of the aggregation ops (every op whose HLO
holds a gather or scatter, and every Pallas kernel), from the trace."""


def read(ctx):
    agg_s = ctx["trace"]["agg_s"]
    if agg_s <= 0 or not ctx["steps"]:
        return None
    return 1e3 * agg_s / ctx["steps"]
