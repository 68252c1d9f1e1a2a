"""Device milliseconds per step under the program's ``mgg.aggregate``
scope, forward and backward, from the traced window (chipbench/scopes.py):
every op the aggregation lowers to, whatever its opcode."""
from chipbench import scopes


def read(ctx):
    agg_s = scopes.read(ctx).get(scopes.AGGREGATE)
    if not agg_s or not ctx["steps"]:
        return None
    return 1e3 * agg_s / ctx["steps"]
