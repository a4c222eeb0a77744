"""paged engine: share of the rows the prefill tower was fed in the window
that were a request's own prompt tokens (the engine's counters
``tower_rows_live`` / ``tower_rows_fed``, each as a delta over the window;
the rest are padding: the tail of a chunk, and rows of a tower wider than
the lanes that prefill).  A program without the counters reads nothing."""


def read(layer):
    if layer.get("kind") != "serve":
        return None
    before = (layer.get("before") or {}).get("engine") or {}
    after = (layer.get("after") or {}).get("engine") or {}
    if "tower_rows_fed" not in before or "tower_rows_fed" not in after:
        return None
    fed = after["tower_rows_fed"] - before["tower_rows_fed"]
    if fed <= 0:
        return None
    live = after["tower_rows_live"] - before["tower_rows_live"]
    return 100.0 * live / float(fed)
