"""paged engine: prompt tokens prefilled per serve step (the engine's
counter ``prompt_tokens_prefilled``, booked at the launch from what the
feed knows, as a delta over the window, over the steps in it).  A program
without the counter reads nothing."""


def read(layer):
    fam = layer.get("family")
    if layer.get("kind") != "serve" or not layer["steps"] \
            or not hasattr(fam, "engine_delta"):
        return None
    n = fam.engine_delta(layer, "prompt_tokens_prefilled")
    return None if n is None else n / float(layer["steps"])
