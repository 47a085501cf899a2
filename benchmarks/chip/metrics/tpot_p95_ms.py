"""tpot_p95_ms: 95th percentile, over requests finished in the window,
of (last token - first token) / (tokens - 1)."""

import readers


def read(ctx):
    v = readers.tpot_s(ctx)
    return 1e3 * readers.p95(v) if v else None
