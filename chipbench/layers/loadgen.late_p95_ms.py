"""95th percentile of how late the load generator sent a request against
the time it was due (a starved generator shows here, not as a fast
server)."""


def read(ctx):
    return ctx["counters"].get("late_p95_ms")
