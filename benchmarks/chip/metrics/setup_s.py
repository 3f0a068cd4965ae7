"""setup_s: seconds from the process's start to the first timed step:
imports, the LMS plan, loading or compiling the step, the seeded state and
the checked steps (host clock)."""


def read(ctx):
    return ctx.setup_s
