"""Device self time per epoch under the validator's `occ.precompute` scope
(the batched MXU distance precompute), per chip, over the traced call's
epochs; nothing where the trace has no `occ.*` scopes."""
import scopes


def read(ctx):
    return scopes.per_epoch_us(ctx, "occ.precompute")
