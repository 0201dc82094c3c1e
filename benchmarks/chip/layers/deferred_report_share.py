"""deferred_report_share: percent of the window's due flows that the
report capacity left for a later period: the sum over the periods of
``reports_due - reports_sent`` over the sum of ``reports_due`` (the
step's per-period ``metrics``). Layer: reporter. Moves fv_per_s."""


def read(ctx):
    due, sent = ctx.get("reports_due"), ctx.get("reports_sent")
    if not due or sent is None:
        return None
    return 100.0 * (due - sent) / due
