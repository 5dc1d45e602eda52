"""XLA compilations inside the window of a training cell (should read 0):
the compile clock's `backend_compile_duration` events."""


def read(ctx):
    c = ctx["counters"]
    return c["compiles"] if "cap_retries" in c else None
