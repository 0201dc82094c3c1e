"""enrich_ms: device time per period of the ops in the program's
``enrich`` scope (history gather, derived features, the verdict head),
mean over the chips. Layer: enrichment. Moves fv_per_s."""
import program_trace


def read(ctx):
    ns = program_trace.stage_ns(ctx["trace"], "enrich")
    return ns / ctx["periods"] / 1e6 if ns and ctx["periods"] else None
