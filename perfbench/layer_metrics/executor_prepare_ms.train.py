"""executor dispatch: median ``executor/prepare`` of a training step
(``Executor.run`` from entry to the looked-up executable: feed conversion,
classification, state gather, key build, cache lookup)."""

from perfbench import ring


def read(layer):
    return ring.median_span_ms(layer, "train", "executor/prepare")
