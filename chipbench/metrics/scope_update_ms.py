"""Device milliseconds per step under the program's ``gnn.update`` scope
(the stages' dense matmul, bias and activation, forward and backward),
from the traced window (chipbench/scopes.py)."""
from chipbench import scopes


def read(ctx):
    s = scopes.read(ctx).get(scopes.UPDATE)
    if not s or not ctx["steps"]:
        return None
    return 1e3 * s / ctx["steps"]
