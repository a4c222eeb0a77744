"""kernels: the GLOBAL layers' paged-attention calls' share of their
roofline: least time of what the tokens the clients received in the
window need in the full-attention layers alone (the family's
``global_attention_need``: a decoded token at context n reads n keys and
values a layer; prompts sent in the window are prefilled in chunks, each
reading every key up to its own end) over the device time of the Mosaic
kernels that take the global group's pools (``paged_attn_global``)."""

from perfbench import flops, layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "global_attention_need"):
        return None
    seconds = sum(k["seconds"] for k in fam.attention_kernels(layer,
                                                              "global"))
    if not seconds:
        return None
    least, _ = flops.least_seconds(
        *fam.global_attention_need(layer["cfg"],
                                   *fam.decoded_and_prefilled(layer)),
        layer["peaks"])
    return 100.0 * least / seconds
