"""device: the share of the named device-idle time
(``trace_reduce.reduce``'s ``idle_gaps``) that lies under a name which is
neither one of the program's spans nor one of the benchmark's: what the
trace still cannot say the host was doing while the chip waited."""

from perfbench import layer_util

OURS = ("gateway/", "scheduler/", "engine/", "executor", "pb:")


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    gaps = (t or {}).get("idle_gaps")
    if not gaps:
        return None
    total = sum(seconds for _name, seconds in gaps)
    if not total:
        return None
    other = sum(seconds for name, seconds in gaps
                if not name.startswith(OURS))
    return 100.0 * other / total
