"""Device milliseconds per traced train step under the ``gemm`` scope:
the matrix products of every linear (``gemm/fwd``, ``gemm/dgrad``,
``gemm/wgrad``) and of the head (``head/gemm``), with whatever XLA
fused into them. See ``_scopes.py``."""
from chipbench.metrics._scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "gemm")
