"""Device ms per decode token of latent attention: ops under the program's
``mla`` scope (projections, latent cache write, absorbed attend, output)
whose midpoint lies in a ``decode.step`` span, over the tokens those steps
made (``harness.op_scopes``)."""
from harness.op_scopes import device_ms_per_token


def read(ctx):
    return device_ms_per_token(ctx, "mla")
