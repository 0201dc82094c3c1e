"""reporter_ms: device time per period of the ops in the program's
``reporter`` scope (ingest, due flows, reports), mean over the chips.
Layer: reporter. Moves events_per_s."""
import program_trace


def read(ctx):
    ns = program_trace.stage_ns(ctx["trace"], "reporter")
    return ns / ctx["periods"] / 1e6 if ns and ctx["periods"] else None
