"""Arithmetic that several per-layer readers share.  A reader gets the
run's ``layer`` dict: ``kind``, ``trace`` (``trace_reduce.reduce``'s
output, empty without a device trace), ``cfg``, ``mix``, ``peaks``,
``e2e``, ``window_s``, ``steps`` and, by kind, the serving cell's
``before``/``after`` counters, ``records`` and ``numbers`` or the
training cell's ``step``.  A reader that finds nothing to read returns
None, and the harness leaves its metric out of the line."""

from __future__ import annotations

from typing import Dict, Optional

from . import flops


def need_trace(layer: Dict, kind: str) -> Optional[Dict]:
    t = layer.get("trace") or {}
    if layer.get("kind") != kind or not t.get("window_s") \
            or not t.get("busy_s"):
        return None
    return t


def idle_share(layer: Dict, kind: str) -> Optional[float]:
    t = need_trace(layer, kind)
    return None if t is None else 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mosaic_time_share(layer: Dict, kind: str) -> Optional[float]:
    t = need_trace(layer, kind)
    if t is None or not t.get("mosaic_calls"):
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]


_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def flash_kernel_kind(kernel: Dict) -> Optional[tuple]:
    """(kind, bh, lq, lk, d, itemsize) of a flash-attention call seen in
    the trace, from its operands' shapes: q, k, v as ``[bh, l, d]`` make a
    forward call; q, k, v, do, o make a backward call, ``dq`` with one
    ``[bh, l, d]`` result and ``dkv`` with two.  None for any other
    kernel."""
    def tensors(shapes, like=None):
        found = [(t, dims) for t, dims in shapes
                 if len(dims) == 3 and t in _ITEMSIZE]
        like = like or (found[0] if found else None)
        # the row statistics ([bh, l, 128] float32) are no q-like tensor
        return [x for x in found
                if x[0] == like[0] and x[1][2] == like[1][2]]

    ins = tensors(kernel["operands"])
    outs = tensors(kernel["results"], ins[0] if ins else None)
    if len(ins) == 3 and len(outs) == 1:
        kind = "fwd"
    elif len(ins) == 5 and len(outs) in (1, 2):
        kind = "dq" if len(outs) == 1 else "dkv"
    else:
        return None
    (dtype, (bh, lq, d)), (_, (_, lk, _)) = ins[0], ins[1]
    return kind, bh, lq, lk, d, _ITEMSIZE[dtype]


def train_attn_roofline(layer: Dict) -> Optional[float]:
    """Least time of the flash calls that the traced steps ran, over their
    device time.  Which calls ran, how often and at which shapes comes
    from the trace itself (so a program that stops running the forward
    kernel twice reads the same share, in half the time); what one call
    needs comes from ``flops.flash_kernel_call``.  The trace does not say
    which calls are causal: one attention op in three is (decoder self),
    so each kind's need is the mean over the three."""
    t = need_trace(layer, "train")
    if t is None:
        return None
    least = seconds = 0.0
    causal = flops.ATTENTION_OPS_CAUSAL
    for kernel in t.get("kernels", []):
        found = flash_kernel_kind(kernel)
        if found is None:
            continue
        per_call = sum(flops.least_seconds(
            *flops.flash_kernel_call(*found, causal=c), layer["peaks"])[0]
            for c in causal) / len(causal)
        least += per_call * kernel["calls"]
        seconds += kernel["seconds"]
    return 100.0 * least / seconds if seconds else None


def serve_attn_roofline(layer: Dict) -> Optional[float]:
    """Least time the ragged paged-attention calls of the window need,
    from what the clients received in it (every decoded token reads its
    own context and its prompt's K/V; every prompt first seen in the
    window is prefilled in chunks), over the kernels' device time."""
    t = need_trace(layer, "serve")
    if t is None or not t.get("mosaic_calls"):
        return None
    lo, hi = layer["t_open"], layer["t_close"]
    decoded, prefilled = [], []
    for rec in layer["records"]:
        plen = len(layer["requests"][rec["id"]]["prompt"])
        decoded += [(pos, plen) for pos, when in enumerate(rec["times"])
                    if lo <= when < hi]
        if rec["sent"] is not None and lo <= rec["sent"] < hi:
            prefilled.append(plen)
    kv_bytes = {"float32": 4, "bfloat16": 2, "int8": 1}[layer["cfg"]["kv_dtype"]]
    ops, bytes_ = flops.ragged_need(layer["cfg"], kv_bytes, decoded,
                                    prefilled, layer["cfg"]["chunk_size"])
    least, _bound = flops.least_seconds(ops, bytes_, layer["peaks"])
    return 100.0 * least / t["mosaic_s"]
