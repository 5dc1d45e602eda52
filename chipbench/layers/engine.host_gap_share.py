"""Share of the traced window in which the device idles while the host is
inside `engine.dispatch`, `engine.retry` or `engine.publish` (the innermost
host span at the gap's midpoint; mean over chips); nothing where the trace
has no `engine.*` spans."""
import scopes


def read(ctx):
    sc = scopes.of_run(ctx)
    if sc is None or not sc.has_engine_spans or sc.window_s <= 0:
        return None
    return 100.0 * sc.idle_under(scopes.HOST_WORK) / sc.window_s
