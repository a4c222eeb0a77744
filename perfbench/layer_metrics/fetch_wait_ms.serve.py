"""paged engine: median ``engine/fetch`` (``lane_step``'s
``np.asarray(next_ids)``: the host blocked on the device; near the
device's own step time when the host is not the limit)."""

from perfbench import ring


def read(layer):
    return ring.median_span_ms(layer, "serve", "engine/fetch")
