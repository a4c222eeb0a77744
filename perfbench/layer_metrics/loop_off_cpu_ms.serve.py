"""scheduler: of the step loop's serial part, what its thread stood off
the CPU: per dispatched round, ``dur - tdur`` of ``scheduler/round`` less
``dur - tdur`` of the ``engine/fetch`` spans under it (waiting for the
interpreter, the offer's sleep, blocking transfers).  The MEAN over the
window's rounds, where ``loop_serial_ms.serve`` is their median: the
chip's host counts a thread's CPU time in ticks of 10 ms, so a round's
``tdur`` is a sample there (0 or 10 ms, over ``dur`` as often as under)
and only the window's sum reads true (400-600 ticks in a traced window
of 6 s: about half a millisecond a round either way).  Nothing
on a program without ``scheduler/round`` or whose spans carry no
``tdur``."""

from perfbench import loop_books


def read(layer):
    return loop_books.mean_per_round_ms(
        layer, lambda r, inner: loop_books.less_fetches(
            r, inner, loop_books.off_cpu))
