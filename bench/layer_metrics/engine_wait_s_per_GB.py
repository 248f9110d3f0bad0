"""Seconds the engine's manager threads blocked pulling results to the
host per GB of user data: the change over the window of every device's
``phase_s[kind]["wait"]``, summed over kinds."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(
        ctx, counters.engine_delta(ctx, counters.phase_s("wait")))
