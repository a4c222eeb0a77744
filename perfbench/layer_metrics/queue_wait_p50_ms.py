"""scheduler: median wait from submit to admission,
``sched.stats()["p50_queue_s"]`` (cumulative since load)."""


def read(layer):
    if layer.get("kind") != "serve" or "p50_queue_s" not in layer["after"]:
        return None
    return 1e3 * layer["after"]["p50_queue_s"]
