"""User bytes of every write issued in the window and acknowledged,
over the time from the window's start to the last acknowledgement
(writers stop issuing at the close and drain).  MB = 10**6 bytes."""


def read(ctx):
    ing = ctx.get("ingest")
    if not ing or not ing["user_bytes"]:
        return None
    return ing["user_bytes"] / (ing["t_end"] - ing["t0"]) / 1e6
