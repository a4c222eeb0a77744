"""Ceiling probes: what can this chip actually sustain?

1. matmul-peak : chained 8k bf16 matmuls — achievable MXU fraction.
2. dispatch   : chained tiny ops — per-step host->device floor.
3. roofline   : ResNet step flops vs bytes from XLA cost analysis.

Run: python tools/perf_probe2.py
"""

from __future__ import annotations

import sys
import time

import numpy as np


def matmul_peak(n=8192, iters=32, trials=3):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(x, w):
        def body(i, x):
            return (x @ w) * (1.0 / n)
        return jax.lax.fori_loop(0, iters, body, x)

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (n, n)).astype(jnp.bfloat16)
    w = jax.random.normal(k2, (n, n)).astype(jnp.bfloat16)
    out = chain(x, w)
    float(out[0, 0].astype(jnp.float32))     # D2H sync
    best = float("inf")
    for _ in range(trials):
        t0 = time.time()
        out = chain(out, w)          # chain on prior output: un-cacheable
        float(out[0, 0].astype(jnp.float32))
        best = min(best, time.time() - t0)
    flops = 2 * n**3 * iters
    return flops / best


def dispatch_floor(steps=200):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tick(x):
        return x + 1.0

    x = jnp.zeros((8, 128), jnp.float32)
    x = tick(x)
    float(x[0, 0])
    t0 = time.time()
    for _ in range(steps):
        x = tick(x)
    float(x[0, 0])                           # D2H sync
    return (time.time() - t0) / steps


def resnet_roofline(batch=256):
    import jax

    sys.path.insert(0, ".")
    from tools.perf_probe import init_resnet50, raw_step_fn

    import jax.numpy as jnp

    rng = jax.random.PRNGKey(0)
    params = jax.device_put(init_resnet50(rng, nhwc=False))
    vel = jax.tree.map(jnp.zeros_like, params)
    x = jnp.ones((batch, 3, 224, 224), jnp.bfloat16)
    y = jnp.zeros((batch,), jnp.int32)
    lowered = jax.jit(raw_step_fn(False)).lower(params, vel, x, y)
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return ca


def main():
    import jax

    jax.config.update("jax_default_matmul_precision", "bfloat16")
    dev = jax.devices()[0].device_kind
    peak = 197e12
    fps = matmul_peak()
    print(f"device={dev}")
    print(f"matmul-peak: {fps/1e12:.1f} TFLOP/s = {fps/peak:.3f} of 197T",
          flush=True)
    dt = dispatch_floor()
    print(f"dispatch floor: {dt*1e6:.0f} us/step", flush=True)
    ca = resnet_roofline()
    fl = ca.get("flops", 0.0)
    by = ca.get("bytes accessed", 0.0)
    print(f"resnet bs256 step: flops={fl/1e9:.1f}G bytes={by/1e9:.2f}GB "
          f"intensity={fl/max(by,1):.0f} flop/byte")
    t_flops = fl / peak
    t_bw = by / 819e9
    print(f"  roofline: t_mxu={t_flops*1e3:.1f}ms t_hbm={t_bw*1e3:.1f}ms "
          f"-> bound={'HBM' if t_bw > t_flops else 'MXU'}; "
          f"best-case mfu={t_flops/max(t_flops, t_bw):.3f}")
    for k in sorted(ca):
        if "bytes" in k or "flops" in k or "seconds" in k:
            print(f"  ca[{k!r}] = {ca[k]:.3e}")


if __name__ == "__main__":
    main()
