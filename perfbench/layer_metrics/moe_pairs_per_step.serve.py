"""expert layer: (token, expert) pairs whose expert is held here, per
serve step, summed over the expert layers (the engine's counter
``moe_pairs_here``, as a delta over the window, over the steps in it).
The counter counts the requests' own tokens: rows of idle lanes and of a
chunk's padding are masked out before routing."""


def read(layer):
    fam = layer.get("family")
    if layer.get("kind") != "serve" or not layer["steps"] \
            or not hasattr(fam, "engine_delta"):
        return None
    pairs = fam.engine_delta(layer, "moe_pairs_here")
    return None if pairs is None else pairs / float(layer["steps"])
