"""scheduler: ``S``, what the step loop does in a round other than wait
for the device: per dispatched round, ``scheduler/round`` less the
``engine/fetch`` spans under it; the median over the window's rounds.
``step_wall_ms.serve`` = ``fetch_wait_ms.serve`` + this, up to what lies
between rounds.  Nothing on a program without ``scheduler/round``."""

from perfbench import loop_books


def read(layer):
    return loop_books.median_per_round_ms(
        layer, lambda r, inner: loop_books.less_fetches(
            r, inner, lambda e: e["dur"]))
