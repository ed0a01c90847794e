"""Device time per decode execution of the ops scoped ``attn/cache_write``:
the new token's key and value written into the cache and the frozen rows
kept, ms (trace, mean over chips)."""

from benchmarks.chip import spans


def read(ctx):
    return spans.decode_scope_ms(ctx, lambda s: s == "attn/cache_write")
