"""kernels: the paged-attention calls' share of their roofline in a model
of global and window layers: least time of what the tokens the clients
received in the window need (the family's ``mixed_attention_need``: a
decoded token at context n reads n keys and values in each global layer
and min(n, window) in each window layer; prompts sent in the window are
prefilled in chunks) over the device time of the Mosaic kernels that take
a KV pool."""

from perfbench import flops, layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "mixed_attention_need"):
        return None
    seconds = sum(k["seconds"] for k in fam.attention_kernels(layer))
    if not seconds:
        return None
    least, _ = flops.least_seconds(
        *fam.mixed_attention_need(layer["cfg"],
                                  *fam.decoded_and_prefilled(layer)),
        layer["peaks"])
    return 100.0 * least / seconds
