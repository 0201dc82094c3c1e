"""collector_ms: device time per period of the ops in the program's
``collector`` scope (validation, ring placement and the copies around
it), mean over the chips. Layer: collector. Moves fv_per_s."""
import program_trace


def read(ctx):
    ns = program_trace.stage_ns(ctx["trace"], "collector")
    return ns / ctx["periods"] / 1e6 if ns and ctx["periods"] else None
