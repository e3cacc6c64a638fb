"""Device milliseconds per traced train step under the ``optim`` scope:
the global norm and clip, the moments, the master update and the cast
back to the params' dtypes. See ``_scopes.py``."""
from chipbench.metrics._scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "optim")
