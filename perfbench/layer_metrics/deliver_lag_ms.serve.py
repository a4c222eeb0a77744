"""scheduler: how far behind the step loop a step's tokens reach their
listeners.  Per ``step``, the end of ``scheduler/deliver_out`` (the
delivery thread has handed the step's record to every stream) less the end
of that step's ``scheduler/deliver`` (the step loop has taken the tokens
and handed the record off, and has the interpreter back after offering it
to the delivery thread); the median over the window's steps.  At or under
zero the record was out before the loop ran on.  A step whose record was
not yet delivered when the window closed is left out;
nothing on a program without the span (the parent of the PR that moved
delivery off the step loop)."""

from perfbench import ring


def read(layer):
    found = ring.events(layer, "serve", "scheduler/deliver",
                        "scheduler/deliver_out")
    if not found:
        return None
    ends = {"scheduler/deliver": {}, "scheduler/deliver_out": {}}
    for e in found:
        step = ring.arg(e, "step")
        if step is not None:
            ends[e["name"]][step] = e["ts"] + e["dur"]
    handed, out = ends["scheduler/deliver"], ends["scheduler/deliver_out"]
    return ring.median_ms(out[s] - handed[s] for s in out if s in handed)
