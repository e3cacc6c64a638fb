"""Device milliseconds per traced train step under the ``attn/core``
scope: the chunked attention's scores, mask, softmax statistics and
product with the values, forward, backward and recomputed. See
``_scopes.py``."""
from chipbench.metrics._scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "attn/core")
