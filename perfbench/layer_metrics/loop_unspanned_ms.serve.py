"""scheduler: what no leaf span of a round covers: per dispatched round,
the summed self time (``dur`` less its direct children's ``dur``) of every
span under ``scheduler/round`` that has children, the round included; the
median over the window's rounds.  With the leaves' own metrics it closes
``loop_serial_ms.serve``.  Nothing on a program without
``scheduler/round``."""

from perfbench import loop_books


def read(layer):
    return loop_books.median_per_round_ms(layer, loop_books.unspanned)
