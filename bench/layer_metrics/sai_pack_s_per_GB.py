"""Seconds the SAI spent packing chunks into padded rows for the engine
per GB of user data, summed over the writes of the window
(``WriteStats.stage_s["pack"]``, a part of the hash stage)."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(ctx, counters.stage_s(ctx, "pack"))
