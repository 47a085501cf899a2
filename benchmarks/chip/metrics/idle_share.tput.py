"""idle_share: share of the traced window in which no operation ran
on the device (%), from the trace."""

import readers


def read(ctx):
    return readers.idle_share(ctx)
