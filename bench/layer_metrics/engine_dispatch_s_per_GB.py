"""Seconds the engine spent handing launches to the device per GB of user
data: ``device_put`` of the inputs and the jitted call, the change over
the window of every device's ``phase_s[kind]["put"]`` and
``phase_s[kind]["call"]``, summed over kinds."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(
        ctx, counters.engine_delta(ctx, counters.phase_s("put", "call")))
