"""gather_enrich_roofline: percent of its roofline the ``gather_enrich*`` Pallas kernel
reaches — the least time of the traced periods' work at the chip's peak
HBM bandwidth (``work.gather_enrich_bytes``) over the kernel's device time.
Layer: enrichment. Moves fv_per_s."""
import trace_reduce
import work


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    ns = trace_reduce.kernel_ns(red, "gather_enrich")
    if not ns:
        return None
    least = work.least_seconds("gather_enrich", ctx["work"], ctx["dfa"],
                               ctx["peaks"])
    return 100.0 * least / (ns / 1e9)
