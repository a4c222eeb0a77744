"""scheduler: per request, ``request/admitted`` less ``request/submitted``
joined on ``rid``; the median over the requests first admitted in the
window (those the clients' median TTFT is taken over, where
``queue_wait_p50_ms`` reads a cumulative median of others; a preempted
request's second admission is no wait in the queue)."""

from perfbench import ring


def read(layer):
    admitted = ring.events(layer, "serve", "request/admitted")
    if not admitted:
        return None
    # a request may have been submitted before the window it was admitted
    # in: look at the whole ring (events() has already refused an
    # overflowed one)
    from paddle_tpu.observability import tracer

    submitted = {ring.arg(e, "rid"): e["ts"]
                 for e in tracer().events(name="request/submitted")}
    return ring.median_ms(e["ts"] - submitted[ring.arg(e, "rid")]
                          for e in admitted
                          if ring.arg(e, "rid") in submitted
                          and not ring.arg(e, "resumed"))
