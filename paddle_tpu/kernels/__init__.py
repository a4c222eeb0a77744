"""Hand-written TPU kernels (Pallas) + sequence-parallel attention.

The reference's hot custom kernels live in paddle/cuda/src/hl_cuda_*.cu and
paddle/operators/math/ (fused LSTM, im2col, softmax...).  On TPU, XLA fusion
covers almost all of those; what it cannot do is (a) O(L) - memory attention
over long sequences (flash attention) and (b) attention over a sequence
sharded across chips — provided in BOTH standard strategies: ring
attention (k/v shards rotate over the ICI; scales past the head count)
and Ulysses all-to-all (two collectives re-shard seq<->heads; lower
latency when heads suffice) — the modern counterpart of the reference's
variable-length-efficiency machinery (LoD batching,
RecurrentGradientMachine).  These are the Pallas kernels.
"""

from .flash_attention import flash_attention, flash_attention_sharded
from .ring_attention import ring_attention, ring_attention_sharded
from .ulysses_attention import (ulysses_attention,
                                ulysses_attention_sharded)

__all__ = ["flash_attention", "flash_attention_sharded", "ring_attention",
           "ring_attention_sharded", "ulysses_attention",
           "ulysses_attention_sharded"]
