"""Op corpus: importing this package registers every op emitter.

The analog of linking paddle/operators/*.cc into the binary — the reference's
USE_OP machinery (op_registry.h) becomes Python imports.
"""

from . import (  # noqa: F401
    activation_ops,
    beam_ops,
    cache_ops,
    control_flow_ops,
    ctc_ops,
    detection_ops,
    io_ops,
    llm_ops,
    crf_ops,
    loss_ops,
    math_ops,
    misc_ops,
    moe_ops,
    nn_ops,
    optimizer_ops,
    quant_ops,
    rnn_ops,
    sequence_ops,
    tensor_ops,
)
