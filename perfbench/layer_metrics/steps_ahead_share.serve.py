"""paged engine: share of the window's serve steps that were launched while
the step before was still in flight, so the host's part of them ran beside
the device (the engine's counters ``steps_ahead`` / ``steps``, each as a
delta over the window: ``PagedLMGenerator.lane_step_ahead``).  A program
without the counter (an engine that fetches every step before it launches
the next) reads nothing."""


def read(layer):
    if layer.get("kind") != "serve":
        return None
    before = (layer.get("before") or {}).get("engine") or {}
    after = (layer.get("after") or {}).get("engine") or {}
    if not all(k in c for k in ("steps", "steps_ahead")
               for c in (before, after)):
        return None
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    return 100.0 * (after["steps_ahead"] - before["steps_ahead"]) \
        / float(steps)
