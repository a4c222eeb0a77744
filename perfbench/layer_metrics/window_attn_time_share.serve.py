"""kernels: device time of the window layers' paged-attention calls (the
Mosaic kernels that take the window group's pools) over device busy time;
``attn_time_share.serve`` less this is the global layers'."""

from perfbench import layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "window_attention_need"):
        return None
    found = fam.attention_kernels(layer, "window")
    if not found:
        return None
    return 100.0 * sum(k["seconds"] for k in found) / t["busy_s"]
