"""scheduler: output tokens the clients received in the window per
scheduler step in it (``sched.stats()["steps"]`` as a delta)."""


def read(layer):
    if layer.get("kind") != "serve" or not layer["steps"]:
        return None
    return layer["numbers"]["tokens_in_window"] / float(layer["steps"])
