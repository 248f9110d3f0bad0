"""Seconds of engine launches per GB of user data: the change over the
window of the sum of every device's ``launch_hist`` (wall time of each
launch, from staging to digests on the host)."""


def _launch_s(stats):
    return sum(row["launch_hist"]["sum_s"]
               for row in stats["per_device"].values())


def read(ctx):
    ing = ctx.get("ingest")
    if not ing or not ing["user_bytes"]:
        return None
    delta = _launch_s(ctx["engine_after"]) - _launch_s(ctx["engine_before"])
    return delta / (ing["user_bytes"] / 1e9)
