"""Seconds the chunk stage waited for CDC fingerprints per GB of user
data, summed over the writes of the window
(``WriteStats.stage_s["fingerprint"]``, a part of the chunk stage)."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(ctx, counters.stage_s(ctx, "fingerprint"))
