"""Adaptive-cap passes re-dispatched at full width over the window
(`OCCEngine.n_cap_retries`, summed over the window's jobs)."""


def read(ctx):
    return ctx["counters"].get("cap_retries")
