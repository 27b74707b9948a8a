"""Process start to the window's start: JAX start-up, traffic generation,
program compile or cache load, warm-up chunks."""


def read(ctx):
    return ctx["setup_s"]
