"""Seconds the engine spent zeroing and filling staging matrices per GB
of user data: the change over the window of every device's
``phase_s[kind]["stage"]``, summed over kinds."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(
        ctx, counters.engine_delta(ctx, counters.phase_s("stage")))
