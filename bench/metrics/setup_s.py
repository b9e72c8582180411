"""setup_s: process start to the first measured pump (building the
engine, compiling or loading programs, settling, warming up)."""


def read(ctx):
    return ctx.setup_s
