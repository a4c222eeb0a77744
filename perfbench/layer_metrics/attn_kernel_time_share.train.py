"""kernels: Mosaic custom calls' device time over device busy time."""

from perfbench import layer_util


def read(layer):
    return layer_util.mosaic_time_share(layer, "train")
