"""paged engine: median ``engine/feed_build`` (``lane_step``: the prefill
and decode feed arrays, filled lane by lane on the host)."""

from perfbench import ring


def read(layer):
    return ring.median_span_ms(layer, "serve", "engine/feed_build")
