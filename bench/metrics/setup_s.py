"""setup_s: process start to the first request of the window, compile,
pool generation, plan build and warm-up included."""


def read(ctx):
    return ctx.setup_s
