"""Mean share of the slots that got a decode token, over the decode steps
inside the window, in % (host record of each step)."""


def read(ctx):
    steps = [s for s in ctx.window_steps() if s.decode_active]
    if not steps:
        return None
    slots = ctx.cfg["serving"]["slots"]
    return 100.0 * sum(s.decode_active for s in steps) / (len(steps) * slots)
