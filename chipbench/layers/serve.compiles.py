"""XLA compilations inside the serving window (should read 0)."""


def read(ctx):
    c = ctx["counters"]
    return c["compiles"] if "bucket_fill" in c else None
