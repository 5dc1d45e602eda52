"""Share of the traced call's device self time, summed over chips, in ops
under no `occ.*` scope: device work outside the OCC pass, and ops the
scopes do not reach; nothing where the trace has no `occ.*` scopes."""
import scopes


def read(ctx):
    sc = scopes.of_run(ctx)
    if sc is None or not sc.scoped or sc.self_seconds <= 0:
        return None
    return 100.0 * sc.scope_seconds(None) / sc.self_seconds
