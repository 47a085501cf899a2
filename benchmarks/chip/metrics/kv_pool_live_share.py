"""kv_pool_live_share: mean over the ticks in the traced span of the KV
pool pages in use over the pool's pages (%), from the args of the
program's ``serve.collect`` span."""

import programtrace


def read(ctx):
    pt = programtrace.of(ctx)
    return programtrace.live_share(pt.spans, ctx.trace.window) if pt else None
