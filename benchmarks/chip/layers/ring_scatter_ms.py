"""ring_scatter_ms: device time of the ``ring_scatter*`` Pallas kernels per period in
the traced window, mean over the chips. Layer: collector. Moves fv_per_s."""
import trace_reduce


def read(ctx):
    red = ctx["trace"]
    if red is None or not ctx["periods"]:
        return None
    ns = trace_reduce.kernel_ns(red, "ring_scatter")
    return ns / ctx["periods"] / 1e6 if ns else None
