"""setup_s: seconds from process start to the opening of the window."""


def read(ctx):
    return ctx.setup_s
