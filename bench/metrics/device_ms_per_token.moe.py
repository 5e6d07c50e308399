"""Device ms per decode token of the expert layers: ops under the program's
``moe`` scope (router, dispatch, held experts, combine, shared experts)
whose midpoint lies in a ``decode.step`` span, over the tokens those steps
made (``harness.op_scopes``)."""
from harness.op_scopes import device_ms_per_token


def read(ctx):
    return device_ms_per_token(ctx, "moe")
