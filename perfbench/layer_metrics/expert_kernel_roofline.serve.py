"""kernels: the grouped expert products' share of their roofline: least
time of what the window's (token, expert) pairs need (the family's
``expert_need``: each pair's three products, and the three matrices of
every expert that got a pair in a step, read once) over the device time of
the Mosaic kernels that take a stacked expert matrix."""

from perfbench import flops, layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "expert_need"):
        return None
    seconds = sum(k["seconds"] for k in fam.expert_kernels(layer))
    pairs = fam.engine_delta(layer, "moe_pairs_here")
    touched = fam.engine_delta(layer, "experts_touched")
    if not seconds or pairs is None or touched is None:
        return None
    least, _ = flops.least_seconds(
        *fam.expert_need(layer["cfg"], pairs, touched), layer["peaks"])
    return 100.0 * least / seconds
