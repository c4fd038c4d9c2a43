"""Share of answered requests' server time spent queued: the server's
summed queue milliseconds over queue plus engine milliseconds
(``Server.metrics()``, window only)."""


def read(ctx):
    c = ctx["counters"]
    total = c["queue_ms_total"] + c["engine_ms_total"]
    return 100.0 * c["queue_ms_total"] / total if total > 0 else None
