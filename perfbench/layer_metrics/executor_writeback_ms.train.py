"""executor dispatch: median ``executor/writeback`` of a training step
(``Executor.run`` after the launch: the new state into the scope, post
host ops, fetch conversion)."""

from perfbench import ring


def read(layer):
    return ring.median_span_ms(layer, "train", "executor/writeback")
