"""Flash-attention kernel probe: fwd+bwd wall-clock and achieved TF/s at
several sequence lengths, pallas vs xla impls.  Run on the real TPU.

Attention flops (causal): fwd 2*b*h*lq*lk*d*2 * 0.5; bwd adds 2.5x fwd
(5 matmuls vs 2) on the live half.  Achieved = flops / time.
"""

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import flash_attention


def sync(x):
    float(jnp.asarray(x).reshape(-1)[0].astype(jnp.float32))  # D2H barrier


def bench_one(b, h, L, d, causal, impl, dtype, block_q, block_k,
              layout="bhld", iters=200, mode="fwdbwd", dropout=0.0):
    r = np.random.RandomState(0)
    if layout == "blhd":
        shape = (b, L, h, d)
    else:
        shape = (b, h, L, d)
    q = jnp.asarray(r.randn(*shape), dtype)
    k = jnp.asarray(r.randn(*shape), dtype)
    v = jnp.asarray(r.randn(*shape), dtype)

    fa = functools.partial(flash_attention, causal=causal, impl=impl,
                           block_q=block_q, block_k=block_k, layout=layout,
                           dropout_rate=dropout, dropout_seed=7 if dropout else None)

    # chain `iters` kernel invocations inside ONE jit: the host's
    # per-dispatch latency would otherwise swamp the kernel
    if mode == "fwd":
        def fn(q, k, v):
            def body(_, q):
                return q + 1e-3 * fa(q, k, v)
            return jax.lax.fori_loop(0, iters, body, q)
    else:
        def fn(q, k, v):
            def body(_, carry):
                q, k, v = carry
                dq, dk, dv = jax.grad(
                    lambda q, k, v: fa(q, k, v).sum(),
                    argnums=(0, 1, 2))(q, k, v)
                return (q + 1e-3 * dq, k + 1e-3 * dk, v + 1e-3 * dv)
            return jax.lax.fori_loop(0, iters, body, (q, k, v))[0]

    fn = jax.jit(fn)
    sync(fn(q, k, v))
    t0 = time.perf_counter()
    sync(fn(q, k, v))
    dt = (time.perf_counter() - t0) / iters

    mm = 2 * b * h * L * L * d * 2          # fwd matmul flops (dense)
    if causal:
        mm *= 0.5
    flops = mm if mode == "fwd" else mm * 3.5   # fwd done inside grad? no:
    # grad-of-sum re-runs fwd (custom_vjp fwd) + bwd 2.5x -> 3.5x fwd
    return dt, flops / dt / 1e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="pallas")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--mode", default="fwdbwd")
    ap.add_argument("--layout", default="bhld")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--h", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--ls", default="256,1024,2048,4096,8192,16384")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--dropout", type=float, default=0.0)
    args = ap.parse_args()
    dtype = jnp.dtype(args.dtype)
    print(f"impl={args.impl} dtype={args.dtype} mode={args.mode} "
          f"layout={args.layout} causal={args.causal} "
          f"d={args.d} h={args.h} device={jax.devices()[0]}")
    for L in [int(x) for x in args.ls.split(",")]:
        b = max(1, args.tokens // L)
        blocks = ([(int(a), int(c)) for a, c in
                   (p.split("/") for p in args.blocks.split(","))]
                  if args.blocks else [(None, None)])
        for bq, bk in blocks:
            dt, tf = bench_one(b, args.h, L, args.d, bool(args.causal),
                               args.impl, dtype, bq, bk, args.layout,
                               mode=args.mode, dropout=args.dropout)
            print(f"L={L:6d} b={b:3d} blocks={bq}/{bk}  "
                  f"{dt*1e3:8.2f} ms  {tf:6.1f} TF/s")


if __name__ == "__main__":
    main()
