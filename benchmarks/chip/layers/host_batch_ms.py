"""host_batch_ms: host time per period spent building the period's event
batch (``TraceReplaySource.next_batch``) and staging it onto the device
(``HostIngestRing.stage``), by the host clock, mean over the traced
window's periods. Layer: host serving loop. Moves events_per_s."""


def read(ctx):
    host = ctx["host_s"]
    return 1e3 * sum(host) / len(host) if host else None
