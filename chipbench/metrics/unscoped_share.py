"""Share of the traced window's busy device time in ops that carry no
program scope, in % (chipbench/scopes.py).  Reads nothing where the
program names no scope at all."""
from chipbench import scopes


def read(ctx):
    secs = scopes.read(ctx)
    busy = sum(secs.values())
    if not busy:
        return None
    return 100.0 * secs.get(scopes.UNSCOPED, 0.0) / busy
