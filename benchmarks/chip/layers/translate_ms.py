"""translate_ms: device time per period of the ops in the program's
``route``, ``exchange`` and ``translate`` scopes (report routing, the
pod exchange, history addressing), mean over the chips. Layer: routing /
translate. Moves fv_per_s."""
import program_trace


def read(ctx):
    ns = program_trace.stage_ns(ctx["trace"], "route", "exchange",
                                "translate")
    return ns / ctx["periods"] / 1e6 if ns and ctx["periods"] else None
