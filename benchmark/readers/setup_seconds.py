"""Process start to the first measured request: data present, server up,
the cell's stacks built, its programs compiled, warm-up done."""


def read(ctx):
    return ctx["setup_s"]
