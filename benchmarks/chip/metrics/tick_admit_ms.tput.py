"""tick_admit_ms: mean milliseconds of the program's ``serve.admit`` span
(cancellations, deadlines, admission and prefill dispatch) per scheduler
tick in the traced span."""

import programtrace


def read(ctx):
    return programtrace.mean_span_ms(ctx, "serve.admit")
