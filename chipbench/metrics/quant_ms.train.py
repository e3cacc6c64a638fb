"""Device milliseconds per traced train step under the ``mor_quant``
scope: every quantization event's amax, GAM scale, selection and
fake-quantization kernel (``mor_quant/<role>``), and the step's MoR
statistics (``mor_quant/stats``). See ``_scopes.py``."""
from chipbench.metrics._scopes import layer_ms


def read(ctx):
    return layer_ms(ctx, "mor_quant")
