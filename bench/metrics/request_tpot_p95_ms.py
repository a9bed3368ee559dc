"""``tpot_p95_ms`` as the end-to-end reading takes it (per request, last
token minus first token over the decode steps, 95th percentile over
every request that arrived in the window, on the client's clock), kept
as a per-layer reading in the cells where its runs spread too widely to
bound: there the tail is set by host stalls in the dispatcher and the
plane, or by the window's last requests, whose final steps run in the
drain in partial decode batches held by the coalescing timer.  Layer:
dispatcher and plane.  Moves ``tpot_p50_ms``."""


def read(ctx):
    return ctx.requests.get("tpot_p95_ms")
