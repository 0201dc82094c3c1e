"""ring_scatter_roofline: percent of its roofline the ``ring_scatter*`` Pallas kernel
reaches — the least time of the traced periods' work at the chip's peak
HBM bandwidth (``work.ring_scatter_bytes``) over the kernel's device time.
Layer: collector. Moves fv_per_s."""
import trace_reduce
import work


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    ns = trace_reduce.kernel_ns(red, "ring_scatter")
    if not ns:
        return None
    least = work.least_seconds("ring_scatter", ctx["work"], ctx["dfa"],
                               ctx["peaks"])
    return 100.0 * least / (ns / 1e9)
