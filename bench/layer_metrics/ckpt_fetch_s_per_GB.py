"""Seconds the store stage spent taking the bytes of the blocks it
claimed (for a device-resident write, their gather on the device and
one pull) per GB of state saved, summed over the window's writes
(``WriteStats.stage_s["fetch"]``, a part of the store stage)."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(ctx, counters.stage_s(ctx, "fetch"))
