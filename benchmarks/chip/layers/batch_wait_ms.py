"""batch_wait_ms: how long a staged batch waits for its step, mean over
the traced periods but the first (which has no step to wait for): from
the end of the period's ``serve/stage`` span to the start of the
``serve/dispatch`` that consumes it. The wait is in
every period's verdict time and in no other metric. Layer: host serving
loop. Moves period_p90_ms."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red.get("batch_wait_ns") is None:
        return None
    return red["batch_wait_ns"] / 1e6
