"""gateway: per request, from the scheduler's first ``request/token`` to
``gateway/first_chunk`` (the stream's consumer has written the first
token's chunk and flushed the socket), joined on ``rid``; the median over
the requests whose first chunk left in the window."""

from perfbench import ring


def read(layer):
    chunks = ring.events(layer, "serve", "gateway/first_chunk")
    if not chunks:
        return None
    # a first token may precede the window its chunk left in: look at the
    # whole ring (events() has already refused an overflowed one)
    from paddle_tpu.observability import tracer

    first = {}
    for e in tracer().events(name="request/token"):
        if ring.arg(e, "index") == 1:
            first.setdefault(ring.arg(e, "rid"), e["ts"])
    waits = [e["ts"] - first[ring.arg(e, "rid")] for e in chunks
             if ring.arg(e, "rid") in first]
    return ring.median_ms(waits)
