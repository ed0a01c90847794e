"""Share of the token gaps inside the window whose step also ran a prefill
chunk, in % (host record of each step)."""


def read(ctx):
    gaps = ctx.gaps()
    if not gaps:
        return None
    return 100.0 * sum(1 for _, s in gaps if s.ran_chunk) / len(gaps)
