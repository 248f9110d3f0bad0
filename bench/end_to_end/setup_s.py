"""Process start to the window's start: imports, building the store,
making the data, compiling or loading every launch shape, and the
cell's own set-up traffic."""


def read(ctx):
    return ctx["setup_s"]
