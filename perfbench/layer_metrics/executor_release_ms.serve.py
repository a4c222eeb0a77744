"""executor dispatch: what ``Executor.run`` frees before it returns, the
median over the window's dispatched steps of ``executor/release`` on the
step loop's thread: the state that went into the step (donated, so dead)
and the signature key built over every variable of it.  Before the span
was there the same time passed unseen as the frame unwound, between
``executor/writeback`` and the end of ``engine/dispatch``.  Nothing on a
program without the span."""

from perfbench import loop_books


def read(layer):
    return loop_books.loop_span_ms(layer, "executor/release")
