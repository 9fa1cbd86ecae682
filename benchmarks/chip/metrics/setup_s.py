"""Process start to the first timed round: inputs from the seed, engine
construction, compilation or cache load, and the first window."""


def read(ctx):
    return ctx.setup_s
