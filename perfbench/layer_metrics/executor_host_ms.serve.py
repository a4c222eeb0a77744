"""executor dispatch: what ``Executor.run`` costs the host around the
launch in a serve step, the median over the window's ``engine/dispatch``
spans of the ``executor/prepare`` + ``executor/writeback`` opened under
each (joined on ``parent``)."""

from perfbench import ring


def read(layer):
    dispatches = ring.events(layer, "serve", "engine/dispatch")
    inner = ring.events(layer, "serve", "executor/prepare",
                        "executor/writeback")
    if not dispatches or not inner:
        return None
    ids = {e["id"] for e in dispatches}
    per_dispatch = ring.summed_by(
        inner, lambda e: e.get("parent") if e.get("parent") in ids else None)
    return ring.median_ms(per_dispatch.values())
