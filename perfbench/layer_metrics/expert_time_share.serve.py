"""kernels: device time of the grouped expert products (the Mosaic kernels
that take a stacked expert matrix) over device busy time."""

from perfbench import layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "expert_kernels"):
        return None
    found = fam.expert_kernels(layer)
    if not found:
        return None
    return 100.0 * sum(k["seconds"] for k in found) / t["busy_s"]
