"""Seconds the engine spent turning pulled results into each job's answer
(``gear_finish``, ``sliding_finish``, slicing digests) per GB of user
data: the change over the window of every device's
``phase_s[kind]["finish"]``, summed over kinds."""
from bench import counters


def read(ctx):
    return counters.per_user_gb(
        ctx, counters.engine_delta(ctx, counters.phase_s("finish")))
