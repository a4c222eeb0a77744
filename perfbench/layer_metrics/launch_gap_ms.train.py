"""executor dispatch: median device-idle gap between one launched step and
the next, from the trace's ``XLA Modules`` line."""

from perfbench import layer_util, stats


def read(layer):
    t = layer_util.need_trace(layer, "train")
    if t is None or not t.get("launch_gaps_s"):
        return None
    return 1e3 * stats.percentile(t["launch_gaps_s"], 50)
