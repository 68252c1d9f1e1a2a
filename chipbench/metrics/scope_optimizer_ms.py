"""Device milliseconds per step under the program's ``train.optimizer``
scope (AdamW with its gradient clipping), from the traced window
(chipbench/scopes.py)."""
from chipbench import scopes


def read(ctx):
    s = scopes.read(ctx).get(scopes.OPTIMIZER)
    if not s or not ctx["steps"]:
        return None
    return 1e3 * s / ctx["steps"]
