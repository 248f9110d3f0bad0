"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / traced window),
averaged over the cell's chips, in percent."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
