"""Device time per decode execution of the ops scoped ``moe/*``: router,
dispatch, experts, exchange and combine, ms (trace, mean over chips)."""

from benchmarks.chip import spans


def read(ctx):
    return spans.decode_scope_ms(ctx, lambda s: s.startswith("moe/"))
