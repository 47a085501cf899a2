"""decode_kv_ms: device milliseconds per decode step of the decode
loop's ops whose innermost program scope is ``kv_cache`` (page-table
upload, page view, paged scatter and gather)."""

import programtrace


def read(ctx):
    return programtrace.decode_scope_ms(ctx, "kv_cache")
