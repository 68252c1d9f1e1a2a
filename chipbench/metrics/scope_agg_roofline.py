"""Share of the aggregation roofline, in %, over the device time under the
program's ``mgg.aggregate`` scope: the least time the chip needs for the
aggregations' required bytes (chipbench/models, at peak HBM bandwidth),
over that scope's device time in the traced window."""
from chipbench import scopes


def read(ctx):
    agg_s = scopes.read(ctx).get(scopes.AGGREGATE)
    if not agg_s or not ctx["steps"]:
        return None
    least = ctx["work"]["agg_bytes"] / ctx["peak"].hbm_bytes_per_s
    return 100.0 * least * ctx["steps"] / agg_s
