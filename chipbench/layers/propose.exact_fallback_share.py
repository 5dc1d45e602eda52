"""Share of the traced call's propose epochs that ran the exact kernel: the
ops under the `occ.propose` scope named after the propose kernel but not
after its screen (`<kernel>`, not `<kernel>_screen`), over the call's
epochs on every chip.  A program without the screen runs the exact kernel
every epoch and reads 100%; one whose screen certifies every row reads 0.

The scope leaves out any kernel a trace holds beside the call's propose
(an assign the service dispatched); the reader counts the whole trace,
which holds the traced call alone, as `state.us_per_epoch` does.  Nothing
where the cell's algorithm has no propose kernel, or no propose kernel op
carries the scope."""
import scopes
import tracing


def read(ctx):
    call = ctx["counters"].get("traced_call")
    red = ctx.get("trace")
    kernel = ctx["algorithm"].PROPOSE_KERNEL
    if not call or not call.get("dir") or red is None or not red.chips \
            or kernel is None:
        return None
    try:
        path = tracing.find_trace(call["dir"])
    except ValueError:
        return None
    chips = scopes.read_file(path)[0][:len(red.chips)]
    epochs = len(call["accepted"]) * len(chips)
    exact = screen = 0
    for chip in chips:
        for _, _, name, tf_op, _ in chip:
            if scopes.scope_of(scopes.path_of(tf_op)) != "occ.propose":
                continue
            name = tracing.op_name(name)
            if name.startswith(kernel + "_screen"):
                screen += 1
            elif name.startswith(kernel):
                exact += 1
    if not epochs or not exact + screen:
        return None
    return 100.0 * exact / epochs
