"""ingest_update_roofline: percent of its roofline the ``ingest_update*`` Pallas kernel
reaches — the least time of the traced periods' work at the chip's peak
HBM bandwidth (``work.ingest_update_bytes``) over the kernel's device time.
Layer: reporter. Moves events_per_s."""
import trace_reduce
import work


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    ns = trace_reduce.kernel_ns(red, "ingest_update")
    if not ns:
        return None
    least = work.least_seconds("ingest_update", ctx["work"], ctx["dfa"],
                               ctx["peaks"])
    return 100.0 * least / (ns / 1e9)
