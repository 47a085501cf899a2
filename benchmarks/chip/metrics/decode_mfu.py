"""decode_mfu: operations the decoded tokens require over the decode
loop's device time at the chip's bf16 peak (%)."""

import readers


def read(ctx):
    return readers.decode_mfu(ctx)
