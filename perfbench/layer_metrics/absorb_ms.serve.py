"""paged engine: what ``lane_step`` does with a fetched step, the median
over the window's dispatched steps of ``engine/absorb`` on the step loop's
thread (prefill bookkeeping, the engine's counters, the emitted tokens)."""

from perfbench import loop_books


def read(layer):
    return loop_books.loop_span_ms(layer, "engine/absorb")
