"""device: 1 - union of device-op intervals over the traced window."""

from perfbench import layer_util


def read(layer):
    return layer_util.idle_share(layer, "serve")
