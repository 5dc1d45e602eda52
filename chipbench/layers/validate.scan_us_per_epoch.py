"""Device self time per epoch under the `occ.scan` scope (the serializing
accept scan, its loop's own time included), per chip, over the traced
call's epochs; nothing where the trace has no `occ.*` scopes."""
import scopes


def read(ctx):
    return scopes.per_epoch_us(ctx, "occ.scan")
