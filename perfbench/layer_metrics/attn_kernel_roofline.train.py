"""kernels: the flash calls' share of their roofline in a training step."""

from perfbench import layer_util


def read(layer):
    return layer_util.train_attn_roofline(layer)
