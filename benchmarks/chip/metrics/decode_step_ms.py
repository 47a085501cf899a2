"""decode_step_ms: device time of the decode-loop program in the trace
divided by the decode steps it ran (runs x decode_chunk)."""

import readers


def read(ctx):
    steps = readers.decode_steps(ctx)
    if not steps:
        return None
    return 1e3 * readers.device_s(
        readers.program_runs(ctx, readers.DECODE_PROGRAM)) / steps
