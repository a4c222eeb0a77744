"""cache manager: window pages given back behind the window per serve
step (the engine's counter ``window_pages_recycled`` as a delta over the
window, over the steps in it)."""


def read(layer):
    fam = layer.get("family")
    if layer.get("kind") != "serve" or not layer["steps"] \
            or not hasattr(fam, "engine_delta"):
        return None
    n = fam.engine_delta(layer, "window_pages_recycled")
    return None if n is None else n / float(layer["steps"])
