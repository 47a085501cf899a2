"""out_tok_s: output tokens delivered in the window over its length."""


def read(ctx):
    rec = ctx.record
    if rec["window_s"] <= 0:
        return None
    return sum(r["n"] for r in rec["requests"]) / rec["window_s"]
