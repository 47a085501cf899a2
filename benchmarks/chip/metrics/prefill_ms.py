"""prefill_ms: device milliseconds per run of the per-slot prefill
program (``jit_prefill_slot`` on the trace's ``XLA Modules`` line)."""

import programtrace
import readers


def read(ctx):
    runs = readers.program_runs(ctx, programtrace.PREFILL_PROGRAM)
    return 1e3 * readers.device_s(runs) / len(runs) if runs else None
