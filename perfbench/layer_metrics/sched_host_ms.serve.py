"""scheduler: the host's share of one serve round outside the engine, the
median over the window's rounds of ``scheduler/admit`` + ``scheduler/plan``
+ ``scheduler/deliver`` + ``scheduler/maintenance`` joined on ``step``
(``ContinuousBatchingScheduler.step_once``).  Rounds that dispatched
nothing (no ``scheduler/deliver``) are left out."""

from perfbench import ring

PHASES = ("scheduler/admit", "scheduler/plan", "scheduler/deliver",
          "scheduler/maintenance")


def read(layer):
    found = ring.events(layer, "serve", *PHASES)
    if not found:
        return None
    stepped = {ring.arg(e, "step") for e in found
               if e["name"] == "scheduler/deliver"} - {None}
    per_step = ring.summed_by(
        found, lambda e: ring.arg(e, "step")
        if ring.arg(e, "step") in stepped else None)
    return ring.median_ms(per_step.values())
