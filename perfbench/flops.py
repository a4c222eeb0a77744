"""Operations and bytes the ALGORITHM needs, computed from shapes — the
benchmark's own count, so that it does not move when the program does.

A multiply-add is 2 operations.  Recomputed work never counts: a backward
pass is twice its forward, whatever the program recomputes.  Causal
attention needs half of the full score matrix.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


# -- the model step -----------------------------------------------------------

def forward_flops(cfg: Dict, batch: int, src_len: int, trg_len: int) -> float:
    """One forward pass of the encoder-decoder over ``batch`` pairs."""
    d, di, v = cfg["d_model"], cfg["d_inner_hid"], cfg["trg_vocab_size"]
    hk = cfg["n_head"] * cfg["d_key"]
    hv = cfg["n_head"] * cfg["d_value"]
    n = cfg["n_layer"]
    s, t = batch * src_len, batch * trg_len      # tokens
    proj = 2 * d * (2 * hk + 2 * hv)             # q, k, v, out per token
    ffn = 2 * 2 * d * di
    enc = n * (s * (proj + ffn)
               + batch * attention_flops(src_len, src_len, hk, hv, False))
    dec = n * (t * (proj + ffn)                          # self + ffn
               + t * 2 * d * (hk + hv)                   # cross q, out
               + s * 2 * d * (hk + hv)                   # cross k, v
               + batch * attention_flops(trg_len, trg_len, hk, hv, True)
               + batch * attention_flops(trg_len, src_len, hk, hv, False))
    head = t * 2 * d * v
    return float(enc + dec + head)


def train_step_flops(cfg: Dict, batch: int, src_len: int, trg_len: int) -> float:
    """Forward plus backward (2x forward); the optimizer is not counted."""
    return 3.0 * forward_flops(cfg, batch, src_len, trg_len)


def matmul_params(cfg: Dict) -> int:
    """Parameters that sit in a matrix product (embedding tables do not)."""
    d, di = cfg["d_model"], cfg["d_inner_hid"]
    hk = cfg["n_head"] * cfg["d_key"]
    hv = cfg["n_head"] * cfg["d_value"]
    attn = d * (2 * hk + 2 * hv)
    return cfg["n_layer"] * (attn + 2 * d * di) \
        + cfg["n_layer"] * (2 * attn + 2 * d * di) + d * cfg["trg_vocab_size"]


# -- attention calls ----------------------------------------------------------

def attention_flops(lq: int, lk: int, hk: int, hv: int, causal: bool) -> float:
    """QK^T and PV of one sequence, all heads (hk = heads x d_key)."""
    full = 2.0 * lq * lk * hk + 2.0 * lq * lk * hv
    return full * (0.5 if causal and lq == lk else 1.0)


FLASH_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}
# rows of d elements moved per batch-head: q-long and k-long tensors
FLASH_ROWS = {"fwd": (2, 2), "dq": (4, 2), "dkv": (3, 4)}


def flash_kernel_call(kind: str, bh: int, lq: int, lk: int, d: int,
                      itemsize: int, causal: bool) -> Tuple[float, float]:
    """(operations, bytes) ONE call of a flash kernel needs, over ``bh``
    batch-heads.  ``fwd`` forms QK^T and PV (2 products of 2 lq lk d
    operations), reads q, k, v and writes o.  The backward kernels keep no
    probabilities, so each must form the scores again: ``dq`` forms S, dP
    and dQ (3 products), reads q, k, v, o, do and writes dq; ``dkv`` forms
    S, dP, dV and dK (4 products), reads the same five and writes dk, dv.
    Every kernel also moves the float32 row statistics once."""
    ops = FLASH_PRODUCTS[kind] * 2.0 * bh * lq * lk * d \
        * (0.5 if causal and lq == lk else 1.0)
    q_long, k_long = FLASH_ROWS[kind]
    rows = q_long * lq + k_long * lk
    return ops, float(bh * d * rows * itemsize + bh * lq * 4)


# whether each attention op of one encoder + decoder layer pair is causal:
# encoder self, decoder self, cross
ATTENTION_OPS_CAUSAL = (False, True, False)


def ragged_need(cfg: Dict, kv_itemsize: int, decoded: List[Tuple[int, int]],
                prefilled: List[int], chunk: int) -> Tuple[float, float]:
    """(operations, bytes) the ragged paged-attention calls of a window
    need.  ``decoded`` holds (position, prompt_len) of every token decoded
    in the window: its self-attention reads position+1 keys and values,
    its cross-attention the prompt's.  ``prefilled`` holds the prompt
    length of every request prefilled in the window: chunk c reads the
    keys up to its own end, causally.  Dead lanes need nothing."""
    h, d, n = cfg["n_head"], cfg["d_key"], cfg["n_layer"]
    kv_row = 2 * h * d * kv_itemsize             # K and V of one position
    ops = bytes_ = 0.0
    for pos, plen in decoded:
        ctx = (pos + 1) + plen
        ops += n * 4.0 * ctx * h * d
        bytes_ += n * ctx * kv_row
    for plen in prefilled:
        done = 0
        while done < plen:
            m = min(chunk, plen - done)
            ctx = done + m
            ops += n * 4.0 * m * (done + (m + 1) / 2.0) * h * d
            bytes_ += n * ctx * kv_row
            done += m
    return ops, bytes_


def least_seconds(ops: float, bytes_: float, peaks: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound it is."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
