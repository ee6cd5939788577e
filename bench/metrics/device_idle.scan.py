"""Share of the traced window in which no operation ran on the device
(1 - busy / window, busy averaged over the chips used), in percent."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
