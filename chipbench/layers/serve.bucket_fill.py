"""Valid rows over padded rows dispatched in the window, from the service's
counters (`bucket_fill_ratio`)."""


def read(ctx):
    v = ctx["counters"].get("bucket_fill")
    return None if v is None else 100.0 * v
