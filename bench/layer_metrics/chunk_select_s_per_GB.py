"""Seconds the chunk stage spent choosing boundaries from fingerprints per
GB of user data, summed over the writes of the window
(``WriteStats.stage_s["select"]``, a part of the chunk stage)."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(ctx, counters.stage_s(ctx, "select"))
