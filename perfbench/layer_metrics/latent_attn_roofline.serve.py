"""kernels: the latent paged-attention calls' share of their roofline:
least time of what the tokens the clients received in the window need
(the family's ``latent_attention_need``, in the absorbed form the kernel
computes: a decoded token at context n reads n latent rows in each layer,
once for all heads, and every head scores each row over its whole width
and sums its leading columns; prompts sent in the window are prefilled in
chunks) over the device time of the Mosaic kernels that take the latent
pool (``paged_attn_latent``)."""

from perfbench import flops, layer_util


def read(layer):
    t = layer_util.need_trace(layer, "serve")
    fam = layer.get("family")
    if t is None or not hasattr(fam, "latent_attention_need"):
        return None
    seconds = sum(k["seconds"] for k in fam.attention_kernels(layer))
    if not seconds:
        return None
    least, _ = flops.least_seconds(
        *fam.latent_attention_need(layer["cfg"],
                                   *fam.decoded_and_prefilled(layer)),
        layer["peaks"])
    return 100.0 * least / seconds
