"""tick_host_ms: mean host milliseconds per Engine.step() outside the
decode chunk (admission, prefill dispatch, collection)."""

import readers


def read(ctx):
    return readers.tick_host_ms(ctx)
