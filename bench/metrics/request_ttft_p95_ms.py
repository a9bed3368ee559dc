"""``ttft_p95_ms`` as the end-to-end reading takes it (due time to first
token, 95th percentile over every request that arrived in the window, on
the client's clock), kept as a per-layer reading where its runs spread
too widely to bound: in a host-bound cell the tail is set by the queue
in the dispatcher and the plane while the host stalls.  Layer:
dispatcher and plane.  Moves ``tpot_p50_ms``: the same stalls hold every
request's decode steps."""


def read(ctx):
    return ctx.requests.get("ttft_p95_ms")
