"""kernels: the ragged paged-attention calls' share of their roofline."""

from perfbench import layer_util


def read(layer):
    return layer_util.serve_attn_roofline(layer)
