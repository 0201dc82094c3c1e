"""idle_share: percent of the traced window in which no operation ran on
the device (1 - union of the device's operation intervals / window),
mean over the chips. Layer: device. Moves events_per_s."""


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])
