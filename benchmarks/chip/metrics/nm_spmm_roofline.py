"""nm_spmm_roofline: the N:M kernel's share of its roofline at decode
M (%), from its device events inside decode-loop runs."""

import readers


def read(ctx):
    return readers.decode_roofline(ctx, "nm_spmm")
