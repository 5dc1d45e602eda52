"""Share of the traced window in which the device idles while the host is
inside `engine.state` (the innermost host span at the gap's midpoint; mean
over chips): the host drawing a call's per-point state before its pass is
dispatched; nothing where the trace has no `engine.state` span."""
import scopes

SPAN = "engine.state"


def read(ctx):
    sc = scopes.of_run(ctx)
    if sc is None or sc.window_s <= 0 \
            or not any(n == SPAN for _, _, n in sc.host):
        return None
    return 100.0 * sc.idle_under((SPAN,)) / sc.window_s
