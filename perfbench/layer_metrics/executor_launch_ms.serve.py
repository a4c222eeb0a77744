"""executor dispatch: the launch of a serve step, the median over the
window's dispatched steps of ``executor_step/infer`` on the step loop's
thread (the span ``record_event`` opens around the compiled call in
``Executor.run``: the jitted call, its feed transfers and its wait for the
interpreter)."""

from perfbench import loop_books


def read(layer):
    return loop_books.loop_span_ms(layer, "executor_step/infer")
