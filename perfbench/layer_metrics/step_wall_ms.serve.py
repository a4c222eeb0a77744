"""paged engine: window seconds per ``lane_step`` (feed build, dispatch,
blocking fetch), steps counted by the scheduler."""


def read(layer):
    if layer.get("kind") != "serve" or not layer["steps"]:
        return None
    return 1e3 * layer["window_s"] / float(layer["steps"])
