"""stale_fv_share: percent of the window's delivered feature vectors
whose report the collector rejected as a sequence duplicate
(``CollectorState.seq_anomalies`` over the rows of the periods' ``mask``):
such a row is enriched from the ring rows the flow already held. Layer:
collector. Moves fv_per_s."""


def read(ctx):
    return 100.0 * ctx["seq_anomalies"] / ctx["fv"] if ctx["fv"] else None
