"""Seconds engine jobs waited in their device's queue per GB of user
data: the change over the window of every device's ``queue_s`` (job
submit to the start of the launch that ran it, summed over jobs)."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(
        ctx, counters.engine_delta(ctx, lambda row: row["queue_s"]))
