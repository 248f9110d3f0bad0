"""Bytes the engine handed to ``device_put`` per byte of user data: the
change over the window of every device's ``h2d_bytes`` over the
window's user bytes.  Padding (power-of-two rows, the length trailer)
is what lifts it above 1."""
from bench import counters


def read(ctx):
    h2d = counters.engine_delta(ctx, lambda row: row["h2d_bytes"])
    if h2d is None or not counters.user_bytes(ctx):
        return None
    return h2d / counters.user_bytes(ctx)
