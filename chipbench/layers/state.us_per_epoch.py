"""Device self time per epoch under the `occ.state` scope (the per-point
state draw of a `partial_fit` call, and the partial-epoch carry's state
joined ahead of it), per chip, over the traced call's epochs.

The state is the call's first device work, and the device's clock can put
it ahead of the `bench.window` span that `scopes.reduce_file` books from
(a recorded trace shows the call's first op 0.1 ms before the span that
dispatched it).  The profiler runs around that one call only, so this
reader books every op of the trace under the scope (its ops are fusions,
with nothing nested in them: an op's time is its self time).  Nothing
where no op carries the scope, as in a program that draws its state
unscoped."""
import scopes
import tracing


def read(ctx):
    call = ctx["counters"].get("traced_call")
    red = ctx.get("trace")
    if not call or not call.get("dir") or red is None or not red.chips:
        return None
    try:
        path = tracing.find_trace(call["dir"])
    except ValueError:
        return None
    chips = scopes.read_file(path)[0][:len(red.chips)]
    epochs = len(call["accepted"])
    ns = sum(end - start for chip in chips
             for start, end, _, tf_op, _ in chip
             if scopes.scope_of(scopes.path_of(tf_op)) == "occ.state")
    if ns <= 0 or not epochs:
        return None
    return 1e-3 * ns / len(chips) / epochs
