"""Device self time per epoch under the `occ.compact` (masking, argsort,
compacting gathers) and `occ.commit` (pool write, scatter-back, writeback,
epoch stats) scopes, per chip, over the traced call's epochs; nothing where
the trace has no `occ.*` scopes."""
import scopes


def read(ctx):
    return scopes.per_epoch_us(ctx, "occ.compact", "occ.commit")
