"""cache manager: share of the global page group's pages in use as the
window closed (the engine's counters ``global_pages_in_use`` /
``global_pages``)."""


def read(layer):
    if layer.get("kind") != "serve":
        return None
    eng = (layer.get("after") or {}).get("engine") or {}
    if not eng.get("global_pages"):
        return None
    return 100.0 * eng["global_pages_in_use"] / float(eng["global_pages"])
