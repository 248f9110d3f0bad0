"""Share of the MD5 kernel's lanes that carried a message, in percent:
the change over the window of every device's ``md5_rows`` (rows used)
over that of ``md5_lane_rows`` (rows computed: the batch padded to a
power of two, then to a whole lane tile)."""
from bench import counters


def read(ctx):
    rows = counters.engine_delta(ctx, lambda row: row["md5_rows"])
    lanes = counters.engine_delta(ctx, lambda row: row["md5_lane_rows"])
    if rows is None or not lanes:
        return None
    return 100.0 * rows / lanes
