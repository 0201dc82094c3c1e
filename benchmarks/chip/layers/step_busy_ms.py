"""step_busy_ms: device-busy time per period (union of the device's
operation intervals in the traced window / periods served in it), mean
over the chips. Layer: SPMD step. Moves period_p90_ms."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not ctx["periods"]:
        return None
    return red["busy_ns"] / ctx["periods"] / 1e6
