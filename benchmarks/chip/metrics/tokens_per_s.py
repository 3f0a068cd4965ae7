"""tokens_per_s: every token trained in the window over the window's whole
time (host clock, the last step synced), per chip."""


def read(ctx):
    return ctx.steps * ctx.tokens_per_step / ctx.window_s / ctx.chips
