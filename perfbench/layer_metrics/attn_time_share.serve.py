"""kernels: device time of the paged-attention calls of both kinds of
layer (the Mosaic kernels that take a KV pool) over device busy time."""

from perfbench import layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "attention_kernels"):
        return None
    found = fam.attention_kernels(layer)
    if not found:
        return None
    return 100.0 * sum(k["seconds"] for k in found) / t["busy_s"]
