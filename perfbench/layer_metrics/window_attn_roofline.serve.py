"""kernels: the WINDOW layers' paged-attention calls' share of their
roofline: least time of what the tokens the clients received in the
window need in the sliding-window layers alone (the family's
``window_attention_need``: a decoded token at context n reads
min(n, window) keys and values a layer; prompts sent in the window are
prefilled in chunks, each reading its own tokens and the window before
them) over the device time of the Mosaic kernels that take the window
group's pools (``paged_attn_window``)."""

from perfbench import flops, layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "window_attention_need"):
        return None
    seconds = sum(k["seconds"] for k in fam.attention_kernels(layer,
                                                              "window"))
    if not seconds:
        return None
    least, _ = flops.least_seconds(
        *fam.window_attention_need(layer["cfg"],
                                   *fam.decoded_and_prefilled(layer)),
        layer["peaks"])
    return 100.0 * least / seconds
