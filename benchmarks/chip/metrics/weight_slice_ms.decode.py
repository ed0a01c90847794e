"""Device time per decode execution of the ops scoped ``layer_weights``:
each layer's weights sliced from the stacked parameters, copies included,
ms (trace, mean over chips)."""

from benchmarks.chip import spans


def read(ctx):
    return spans.decode_scope_ms(ctx, lambda s: s == "layer_weights")
