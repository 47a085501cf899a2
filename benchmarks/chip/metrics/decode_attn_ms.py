"""decode_attn_ms: device milliseconds per decode step of the decode
loop's ops whose innermost program scope is ``attention``, custom calls
(the projection kernels) left out."""

import programtrace


def read(ctx):
    return programtrace.decode_scope_ms(ctx, "attention", custom_calls=False)
