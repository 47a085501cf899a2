"""tick_collect_ms: mean milliseconds of the program's ``serve.collect``
span (numeric guard, token distribution, retirement, end of chunk) per
tick with a decode chunk in the traced span."""

import programtrace


def read(ctx):
    return programtrace.mean_span_ms(ctx, "serve.collect")
