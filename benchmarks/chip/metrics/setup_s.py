"""setup_s: process start to the first timed request (s), compiles
included — host clock."""


def read(ctx):
    return ctx.record["setup_s"]
