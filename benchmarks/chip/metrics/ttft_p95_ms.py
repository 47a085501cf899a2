"""ttft_p95_ms: 95th percentile, over every request due (open loop)
or submitted (closed loop) in the window, of the time from then to the
delivery of its first token; a request with none by the window's end
counts with its elapsed time."""

import readers


def read(ctx):
    v = readers.ttft_s(ctx)
    return 1e3 * readers.p95(v) if v else None
