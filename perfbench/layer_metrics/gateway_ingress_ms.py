"""gateway: median ``gateway/ingress`` (``Gateway.submit``: from the
request's parsed arguments through rate limit, journal and
``sched.submit`` to the scheduler's queue)."""

from perfbench import ring


def read(layer):
    return ring.median_span_ms(layer, "serve", "gateway/ingress")
