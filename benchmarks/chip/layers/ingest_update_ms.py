"""ingest_update_ms: device time of the ``ingest_update*`` Pallas kernels per period in
the traced window, mean over the chips. Layer: reporter. Moves events_per_s."""
import trace_reduce


def read(ctx):
    red = ctx["trace"]
    if red is None or not ctx["periods"]:
        return None
    ns = trace_reduce.kernel_ns(red, "ingest_update")
    return ns / ctx["periods"] / 1e6 if ns else None
