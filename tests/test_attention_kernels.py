"""Flash attention + ring attention vs a naive reference.

Mirrors the reference's Compare2Function CPU-vs-GPU pattern
(paddle/function/FunctionTest.h): the naive full-matrix softmax attention is
the golden; the blocked/ring implementations must match in forward and grad.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import (flash_attention, ring_attention,
                                ring_attention_sharded)
from paddle_tpu.parallel import make_mesh


def naive_attention(q, k, v, bias=None, causal=False, sm_scale=None):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        lq, lk = s.shape[-2:]
        mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def make_qkv(b=2, h=3, lq=64, lk=64, d=16, seed=0):
    r = np.random.RandomState(seed)
    q = r.randn(b, h, lq, d).astype(np.float32)
    k = r.randn(b, h, lk, d).astype(np.float32)
    v = r.randn(b, h, lk, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_xla_matches_naive(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          impl="xla")
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_xla_bias():
    q, k, v = make_qkv()
    r = np.random.RandomState(1)
    bias = jnp.asarray(r.randn(2, 1, 64, 64).astype(np.float32))
    out = flash_attention(q, k, v, bias=bias, block_q=16, block_k=16,
                          impl="xla")
    ref = naive_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_naive(causal):
    q, k, v = make_qkv(lq=32, lk=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                               impl="xla").sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=causal).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_bias_grad():
    q, k, v = make_qkv(lq=32, lk=32)
    bias = jnp.asarray(np.random.RandomState(1).randn(1, 3, 32, 32)
                       .astype(np.float32))
    g1 = jax.grad(lambda b: flash_attention(
        q, k, v, bias=b, block_q=8, block_k=8, impl="xla").sum())(bias)
    g2 = jax.grad(lambda b: naive_attention(q, k, v, bias=b).sum())(bias)
    np.testing.assert_allclose(g1, g2, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_interpret_matches_naive(causal):
    # pallas kernel semantics validated in interpreter mode on CPU — the
    # same kernel compiles for real on TPU (impl='pallas')
    q, k, v = make_qkv(b=1, h=2, lq=32, lk=32, d=8)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                          impl="pallas_interpret")
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_pallas_interpret_bias():
    q, k, v = make_qkv(b=2, h=2, lq=32, lk=32, d=8)
    bias = jnp.asarray(np.random.RandomState(1).randn(2, 1, 32, 32)
                       .astype(np.float32))
    out = flash_attention(q, k, v, bias=bias, block_q=16, block_k=16,
                          impl="pallas_interpret")
    ref = naive_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# ring attention on the virtual 8-device mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_naive(causal):
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=2, lq=32, lk=32, d=8)
    out = ring_attention_sharded(mesh, q, k, v, causal=causal,
                                 dp_axis=None)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_ring_attention_bias():
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=2, lq=32, lk=32, d=8)
    # padding-style bias: rows local-shardable, columns global
    bias = np.zeros((2, 1, 32, 32), np.float32)
    bias[:, :, :, 28:] = -1e9
    bias = jnp.asarray(bias)
    out = ring_attention_sharded(mesh, q, k, v, bias=bias, dp_axis=None)
    ref = naive_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_ring_attention_grad():
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=1, h=2, lq=32, lk=32, d=8)

    def loss_ring(q, k, v):
        return ring_attention_sharded(mesh, q, k, v, causal=True,
                                      dp_axis=None).sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    # jitted: an eager shard_map dispatches (and compiles) op by op
    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_attention_dp_sp_mesh():
    # combined data parallel x sequence parallel
    mesh = make_mesh({"dp": 2, "sp": 4}, jax.devices()[:8])
    q, k, v = make_qkv(b=2, h=2, lq=32, lk=32, d=8)
    out = ring_attention_sharded(mesh, q, k, v, causal=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_flash_non_divisible_lengths():
    # lengths not a multiple of the block: entry pads + masks (regression:
    # the xla path used to silently truncate tail keys)
    q, k, v = make_qkv(lq=48, lk=48, d=8)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          impl="xla")
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, impl="xla").sum())(q)
    g2 = jax.grad(lambda q: naive_attention(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=5e-4, rtol=5e-4)


def test_flash_non_divisible_bias_grad():
    q, k, v = make_qkv(lq=48, lk=48, d=8)
    bias = jnp.asarray(np.random.RandomState(1).randn(2, 1, 48, 48)
                       .astype(np.float32))
    g1 = jax.grad(lambda b: flash_attention(
        q, k, v, bias=b, block_q=32, block_k=32, impl="xla").sum())(bias)
    g2 = jax.grad(lambda b: naive_attention(q, k, v, bias=b).sum())(bias)
    np.testing.assert_allclose(g1, g2, atol=5e-4, rtol=5e-4)


# ---------------------------------------------------------------------------
# in-kernel attention-probability dropout (VERDICT r1 weak#4)
# ---------------------------------------------------------------------------

def naive_dropout_attention(q, k, v, seed, rate, bias=None, causal=False):
    """Golden: dense softmax attention with the SAME hash mask the kernels
    use, applied to the normalised probabilities (inverted dropout)."""
    from paddle_tpu.kernels.flash_attention import keep_scale
    b, h, lq, _ = q.shape
    lk = k.shape[2]
    sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    bh = (jnp.arange(b, dtype=jnp.int32)[:, None] * h +
          jnp.arange(h, dtype=jnp.int32)[None, :])[:, :, None, None]
    rows = jnp.arange(lq, dtype=jnp.int32)[None, None, :, None]
    cols = jnp.arange(lk, dtype=jnp.int32)[None, None, None, :]
    scale = keep_scale(jnp.uint32(seed), bh, rows, cols, rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p * scale,
                      v.astype(jnp.float32)).astype(q.dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_matches_hash_reference(causal):
    q, k, v = make_qkv(lq=32, lk=32, d=8)
    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8,
                          impl="xla", dropout_rate=0.3, dropout_seed=7)
    ref = naive_dropout_attention(q, k, v, seed=7, rate=0.3, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # and the grads: fwd custom-vjp vs jax AD through the dense reference
    g1 = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=8, block_k=8, impl="xla",
        dropout_rate=0.3, dropout_seed=7).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: naive_dropout_attention(
        q, k, v, seed=7, rate=0.3, causal=causal).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_dropout_bias_grad():
    q, k, v = make_qkv(lq=32, lk=32, d=8)
    bias = jnp.asarray(np.random.RandomState(1).randn(2, 1, 32, 32)
                       .astype(np.float32))
    g1 = jax.grad(lambda b: flash_attention(
        q, k, v, bias=b, block_q=8, block_k=8, impl="xla",
        dropout_rate=0.2, dropout_seed=3).sum())(bias)
    g2 = jax.grad(lambda b: naive_dropout_attention(
        q, k, v, seed=3, rate=0.2, bias=b).sum())(bias)
    np.testing.assert_allclose(g1, g2, atol=5e-4, rtol=5e-4)


def test_flash_dropout_pallas_interpret_matches_xla():
    # the pallas kernel's in-kernel hash mask must equal the XLA path's —
    # that is what makes the custom-vjp backward consistent on TPU
    q, k, v = make_qkv(b=1, h=2, lq=32, lk=32, d=8)
    out_p = flash_attention(q, k, v, block_q=16, block_k=16,
                            impl="pallas_interpret",
                            dropout_rate=0.25, dropout_seed=11)
    out_x = flash_attention(q, k, v, block_q=16, block_k=16, impl="xla",
                            dropout_rate=0.25, dropout_seed=11)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               atol=2e-5, rtol=2e-5)


def test_flash_dropout_statistics():
    # ~rate of the attention mass is dropped; mean is preserved (inverted)
    q, k, v = make_qkv(b=4, h=4, lq=64, lk=64, d=8)
    clean = flash_attention(q, k, v, impl="xla")
    drop = flash_attention(q, k, v, impl="xla", dropout_rate=0.5,
                           dropout_seed=123)
    assert not np.allclose(np.asarray(clean), np.asarray(drop))
    # different seeds give different masks; same seed reproduces
    drop2 = flash_attention(q, k, v, impl="xla", dropout_rate=0.5,
                            dropout_seed=124)
    drop_same = flash_attention(q, k, v, impl="xla", dropout_rate=0.5,
                                dropout_seed=123)
    assert not np.allclose(np.asarray(drop), np.asarray(drop2))
    np.testing.assert_array_equal(np.asarray(drop), np.asarray(drop_same))


def test_keep_scale_rate():
    from paddle_tpu.kernels.flash_attention import keep_scale
    rows = jnp.arange(512, dtype=jnp.int32)[:, None]
    cols = jnp.arange(512, dtype=jnp.int32)[None, :]
    sc = keep_scale(jnp.uint32(42), jnp.int32(0), rows, cols, 0.3)
    frac_dropped = float((sc == 0).mean())
    assert abs(frac_dropped - 0.3) < 0.01


def test_ring_dropout_runs_and_differs():
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=2, lq=32, lk=32, d=8)
    # jitted: an eager shard_map dispatches (and compiles) op by op
    clean = jax.jit(lambda q: ring_attention_sharded(
        mesh, q, k, v, dp_axis=None))(q)
    dropped = jax.jit(lambda q: ring_attention_sharded(
        mesh, q, k, v, dp_axis=None, dropout_rate=0.4, dropout_seed=5))
    drop = dropped(q)
    assert not np.allclose(np.asarray(clean), np.asarray(drop))
    # deterministic given the seed, and differentiable
    np.testing.assert_array_equal(np.asarray(drop), np.asarray(dropped(q)))
    g = jax.jit(jax.grad(lambda q: dropped(q).sum()))(q)
    assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# Pallas backward kernels + layouts (r4: bwd moved from XLA scan to Pallas)
# ---------------------------------------------------------------------------

@pytest.fixture
def pallas_bwd(monkeypatch):
    """Route even tiny shapes through the dq/dkv Pallas kernels (production
    keeps the XLA-scan backward below PALLAS_BWD_MIN_L)."""
    import importlib
    mod = importlib.import_module("paddle_tpu.kernels.flash_attention")
    monkeypatch.setattr(mod, "PALLAS_BWD_MIN_L", 0)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_grad_matches_naive(causal, pallas_bwd):
    # bias-free grads route through the dq/dkv Pallas kernels
    q, k, v = make_qkv(b=1, h=2, lq=32, lk=32, d=8)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16,
                               block_k=16, impl="pallas_interpret").sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=causal).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_pallas_grad_weighted_cotangent(pallas_bwd):
    # non-uniform do exercises delta = rowsum(o*do) properly
    q, k, v = make_qkv(b=1, h=2, lq=32, lk=32, d=8)
    w = jnp.asarray(np.random.RandomState(5).randn(1, 2, 32, 8)
                    .astype(np.float32))

    def loss(fn):
        def f(q, k, v):
            return (fn(q, k, v) * w).sum()
        return f

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=8, block_k=8,
        impl="pallas_interpret"))
    naive = loss(lambda q, k, v: naive_attention(q, k, v, causal=True))
    g1 = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_pallas_dropout_grad_matches_xla(pallas_bwd):
    # in-kernel hash dropout: pallas bwd mask must equal the XLA path's
    q, k, v = make_qkv(b=1, h=2, lq=32, lk=32, d=8)

    def g(impl):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, block_q=16, block_k=16, impl=impl,
            dropout_rate=0.25, dropout_seed=11).sum(),
            argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(g("pallas_interpret"), g("xla")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_pallas_non_divisible_kv_len(pallas_bwd):
    # padding is masked by the static kv_len bound inside the kernels (no
    # synthetic bias tensor) — fwd and grad
    q, k, v = make_qkv(b=1, h=2, lq=40, lk=40, d=8)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          impl="pallas_interpret")
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32,
        impl="pallas_interpret").sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: naive_attention(
        q, k, v, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "mp": 2}])
def test_flash_sharded_matches_unsharded(axes, pallas_bwd, fresh_programs):
    """On a data / tensor-parallel mesh the Pallas flash kernels run
    inside a shard_map over the batch and head axes (a Mosaic call cannot
    be auto-partitioned) — outputs AND gradients equal the unsharded
    kernels', called directly and through the fused_attention op under
    ``mesh_guard`` (where ``kernel_axes`` picks the axes)."""
    from paddle_tpu import fluid, parallel
    from paddle_tpu.kernels import flash_attention_sharded

    mesh = make_mesh(axes, jax.devices()[:4])
    q, k, v = make_qkv(b=4, h=2, lq=32, lk=32, d=8)
    b_ax, h_ax = parallel.kernel_axes(mesh, batch=4, heads=2)
    assert (b_ax, h_ax) == ("dp", "mp" if "mp" in axes else None)
    kw = dict(causal=True, impl="pallas_interpret")

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) ** 2).sum()

    def sharded(q, k, v):
        return flash_attention_sharded(mesh, q, k, v, batch_axis=b_ax,
                                       head_axis=h_ax, **kw)

    def plain(q, k, v):
        return flash_attention(q, k, v, **kw)

    np.testing.assert_allclose(jax.jit(sharded)(q, k, v), plain(q, k, v),
                               atol=1e-6, rtol=1e-6)
    g1 = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    main, startup, scope = fresh_programs
    names = ("q", "k", "v")
    out = fluid.layers.fused_attention(
        *(fluid.layers.data(n, [2, 32, 8], "float32") for n in names), **kw)
    feed = {n: np.asarray(x) for n, x in zip(names, (q, k, v))}
    exe = fluid.Executor(fluid.CPUPlace())
    with parallel.mesh_guard(mesh):
        via_op, = exe.run(main, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(via_op), plain(q, k, v),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_flash_blhd_layout_matches_bhld(impl, pallas_bwd):
    # layout='blhd' takes [b, l, h, d] directly — no split-heads transposes
    q, k, v = make_qkv(b=2, h=2, lq=32, lk=32, d=8)
    qt = jnp.transpose(q, (0, 2, 1, 3))        # -> [b, l, h, d]
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = flash_attention(qt, kt, vt, causal=True, block_q=16, block_k=16,
                          impl=impl, layout="blhd")
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(jnp.transpose(out, (0, 2, 1, 3)), ref,
                               atol=2e-5, rtol=2e-5)

    g1 = jax.grad(lambda x: flash_attention(
        x, kt, vt, causal=True, block_q=16, block_k=16, impl=impl,
        layout="blhd").sum())(qt)
    g2 = jax.grad(lambda x: naive_attention(x, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(jnp.transpose(g1, (0, 2, 1, 3)), g2,
                               atol=5e-4, rtol=5e-4)


def test_flash_pallas_rect_blocks_and_lengths(pallas_bwd):
    # lq != lk and block_q != block_k through the pallas kernels
    q, k, v = make_qkv(b=1, h=2, lq=32, lk=64, d=8)
    out = flash_attention(q, k, v, block_q=16, block_k=32,
                          impl="pallas_interpret")
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda k: flash_attention(
        q, k, v, block_q=16, block_k=32,
        impl="pallas_interpret").sum())(k)
    g2 = jax.grad(lambda k: naive_attention(q, k, v).sum())(k)
    np.testing.assert_allclose(g1, g2, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_flash_block_offsets(impl, pallas_bwd):
    """block_offsets place the q/k blocks at global positions: the causal
    mask and the dropout hash must behave as if the blocks were slices of
    one long sequence (the contract ring attention relies on)."""
    full_q, full_k, full_v = make_qkv(b=1, h=2, lq=64, lk=64, d=8)
    ro, co = 32, 16            # q block = rows 32..63, k block = cols 16..47
    q = full_q[:, :, 32:64]
    k = full_k[:, :, 16:48]
    v = full_v[:, :, 16:48]

    # causal: out == the corresponding tile of the full causal attention
    # restricted to these keys
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (8 ** -0.5)
    rows = (ro + jnp.arange(32))[:, None]
    cols = (co + jnp.arange(32))[None, :]
    s = jnp.where(rows >= cols, s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                     v.astype(jnp.float32))
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          impl=impl, block_offsets=(ro, co))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    # grads flow and match the dense reference
    g1 = jax.grad(lambda k_: flash_attention(
        q, k_, v, causal=True, block_q=16, block_k=16, impl=impl,
        block_offsets=(ro, co)).sum())(k)

    def dense(k_):
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k_.astype(jnp.float32)) * (8 ** -0.5)
        s_ = jnp.where(rows >= cols, s_, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(s_, axis=-1),
                          v.astype(jnp.float32)).sum()

    g2 = jax.grad(dense)(k)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=5e-4, rtol=5e-4)

    # dropout hash keys on GLOBAL positions: the offset call equals the
    # corresponding slice semantics of the hash mask
    outd = flash_attention(q, k, v, block_q=16, block_k=16, impl=impl,
                           dropout_rate=0.3, dropout_seed=5,
                           block_offsets=(ro, co))
    refd = naive_dropout_attention_tile(q, k, v, seed=5, rate=0.3,
                                        row_off=ro, col_off=co)
    np.testing.assert_allclose(np.asarray(outd), np.asarray(refd),
                               atol=2e-5, rtol=2e-5)


def naive_dropout_attention_tile(q, k, v, seed, rate, row_off, col_off):
    from paddle_tpu.kernels.flash_attention import keep_scale
    b, h, lq, _ = q.shape
    lk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    p = jax.nn.softmax(s, axis=-1)
    bh = (jnp.arange(b, dtype=jnp.int32)[:, None] * h +
          jnp.arange(h, dtype=jnp.int32)[None, :])[:, :, None, None]
    rows = (row_off + jnp.arange(lq, dtype=jnp.int32))[None, None, :, None]
    cols = (col_off + jnp.arange(lk, dtype=jnp.int32))[None, None, None, :]
    scale = keep_scale(jnp.uint32(seed), bh, rows, cols, rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p * scale, v.astype(jnp.float32))


def test_ring_flash_chunks_match_unsharded_flash():
    """The r4 ring (flash kernels per held block, offset masks, lse merge)
    must equal the UNSHARDED flash kernel bit-for-bit in semantics — same
    causal mask, same global-position dropout hash — for both values and
    gradients."""
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=2, lq=64, lk=64, d=8, seed=3)

    # jitted: an eager shard_map dispatches (and compiles) op by op
    def ring_fn(v_):
        return ring_attention_sharded(mesh, q, k, v_, causal=True,
                                      dp_axis=None, dropout_rate=0.25,
                                      dropout_seed=42)

    ring = jax.jit(ring_fn)(v)
    flat = flash_attention(q, k, v, causal=True, impl="xla",
                           dropout_rate=0.25, dropout_seed=42)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(flat),
                               atol=2e-5, rtol=2e-5)

    g_ring = jax.jit(jax.grad(lambda v_: ring_fn(v_).sum()))(v)
    g_flat = jax.grad(lambda v_: flash_attention(
        q, k, v_, causal=True, impl="xla", dropout_rate=0.25,
        dropout_seed=42).sum())(v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_flat),
                               atol=5e-4, rtol=5e-4)


def test_ring_non_divisible_shards():
    """Local shards that don't divide the kernel blocks pad + mask inside
    the ring (kv_len on local columns, offsets on global ones)."""
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=1, h=2, lq=24, lk=24, d=8, seed=6)  # shards of 6
    # jitted: an eager shard_map dispatches (and compiles) op by op
    def ring_fn(q_):
        return ring_attention_sharded(mesh, q_, k, v, causal=True,
                                      dp_axis=None)

    out = jax.jit(ring_fn)(q)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    g1 = jax.jit(jax.grad(lambda q_: ring_fn(q_).sum()))(q)
    g2 = jax.grad(lambda q_: naive_attention(q_, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_flash_dead_rows_zero_output(impl):
    """Dead-row contract (r4 ADVICE): causal + block_offsets placing the
    whole k/v block strictly after the queries means every row has zero
    live keys — both impls must return output 0 and lse +inf (observable
    here as exactly-zero output and zero gradient), not uniform-attention
    garbage over masked keys."""
    q, k, v = make_qkv(b=1, h=2, lq=16, lk=16, d=8, seed=9)
    out = flash_attention(q, k, v, causal=True, impl=impl, block_q=8,
                          block_k=8, block_offsets=(0, 16))
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    g = jax.grad(lambda v_: flash_attention(
        q, k, v_, causal=True, impl=impl, block_q=8, block_k=8,
        block_offsets=(0, 16)).sum())(v)
    assert np.all(np.isfinite(np.asarray(g)))
    np.testing.assert_array_equal(np.asarray(g), 0.0)

    # mixed: kv block straddles the diagonal — live rows still match the
    # naive softmax over their visible keys, dead rows are zero
    out2 = flash_attention(q, k, v, causal=True, impl=impl, block_q=8,
                           block_k=8, block_offsets=(0, 8))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    rows = jnp.arange(16)[:, None]; cols = 8 + jnp.arange(16)[None, :]
    sm = jnp.where(rows >= cols, s, -jnp.inf)
    ref = jnp.einsum("bhqk,bhkd->bhqd",
                     jnp.where(rows[None, None] >= 8,
                               jax.nn.softmax(sm, axis=-1), 0.0), v)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Ulysses all-to-all sequence parallelism on the virtual mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_naive(causal):
    from paddle_tpu.kernels import ulysses_attention_sharded

    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=4, lq=32, lk=32, d=8)
    out = ulysses_attention_sharded(mesh, q, k, v, causal=causal,
                                    dp_axis=None)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_ulysses_attention_bias():
    from paddle_tpu.kernels import ulysses_attention_sharded

    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=4, lq=32, lk=32, d=8)
    bias = np.zeros((2, 1, 32, 32), np.float32)
    bias[:, :, :, 28:] = -1e9       # padding mask, columns global
    bias = jnp.asarray(bias)
    out = ulysses_attention_sharded(mesh, q, k, v, bias=bias,
                                    dp_axis=None)
    ref = naive_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_ulysses_attention_grad():
    from paddle_tpu.kernels import ulysses_attention_sharded

    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=1, h=4, lq=32, lk=32, d=8)

    def loss_uly(q, k, v):
        return ulysses_attention_sharded(mesh, q, k, v, causal=True,
                                         dp_axis=None).sum()

    def loss_naive(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    # jitted: an eager shard_map dispatches (and compiles) op by op
    g1 = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), b, atol=5e-4, rtol=5e-4)


def test_ulysses_dp_sp_mesh():
    """Combined dp x sp mesh: batch and sequence sharded together."""
    from paddle_tpu.kernels import ulysses_attention_sharded

    mesh = make_mesh({"dp": 2, "sp": 2}, jax.devices()[:4])
    q, k, v = make_qkv(b=4, h=2, lq=32, lk=32, d=8)
    out = ulysses_attention_sharded(mesh, q, k, v, causal=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_non_divisible_heads():
    from paddle_tpu.kernels import ulysses_attention_sharded

    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=1, h=3, lq=32, lk=32, d=8)
    with pytest.raises(ValueError, match="head count"):
        ulysses_attention_sharded(mesh, q, k, v)


def test_fused_attention_op_ulysses_matches_single(fresh_programs):
    """The fused_attention op routes sp_impl='ulysses' under an sp mesh
    and matches the meshless run."""
    from paddle_tpu import fluid, parallel

    main, startup, scope = fresh_programs
    q = fluid.layers.data("q", [4, 32, 8], "float32")
    k = fluid.layers.data("k", [4, 32, 8], "float32")
    v = fluid.layers.data("v", [4, 32, 8], "float32")
    out = fluid.layers.fused_attention(q, k, v, causal=True,
                                       seq_parallel=True,
                                       sp_impl="ulysses")
    qv, kv, vv = make_qkv(b=2, h=4, lq=32, lk=32, d=8)
    feed = {"q": np.asarray(qv), "k": np.asarray(kv), "v": np.asarray(vv)}
    exe = fluid.Executor(fluid.CPUPlace())
    single, = exe.run(main, feed=feed, fetch_list=[out])
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    with parallel.mesh_guard(mesh):
        sharded, = exe.run(main, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_headful_bias():
    """A bias with a full head axis is sliced to each device's
    post-all-to-all head tile (the transformer's materialised attn-bias
    path)."""
    from paddle_tpu.kernels import ulysses_attention_sharded

    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=4, lq=32, lk=32, d=8)
    bias = np.random.RandomState(3).randn(2, 4, 32, 32).astype(
        np.float32) * 0.5
    bias = jnp.asarray(bias)
    out = ulysses_attention_sharded(mesh, q, k, v, bias=bias,
                                    dp_axis=None)
    ref = naive_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_ulysses_dropout_runs_and_differs():
    """Ulysses attention-prob dropout: deterministic per seed,
    differentiable, and the head-tile masks are decorrelated — no two
    sequence shards (= head tiles after the all-to-all) produce
    identical keep patterns."""
    from paddle_tpu.kernels import ulysses_attention_sharded

    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    q, k, v = make_qkv(b=2, h=4, lq=32, lk=32, d=8)
    # jitted: an eager shard_map dispatches (and compiles) op by op
    clean = jax.jit(lambda q: ulysses_attention_sharded(
        mesh, q, k, v, dp_axis=None))(q)
    dropped = jax.jit(lambda q: ulysses_attention_sharded(
        mesh, q, k, v, dp_axis=None, dropout_rate=0.4, dropout_seed=5))
    drop = dropped(q)
    assert not np.allclose(np.asarray(clean), np.asarray(drop))
    np.testing.assert_array_equal(np.asarray(drop), np.asarray(dropped(q)))
    # decorrelation across head tiles: with IDENTICAL q/k/v per head,
    # identical masks would give identical per-head outputs
    q1 = jnp.broadcast_to(q[:, :1], q.shape)
    k1 = jnp.broadcast_to(k[:, :1], k.shape)
    v1 = jnp.broadcast_to(v[:, :1], v.shape)
    d1 = np.asarray(jax.jit(lambda *a: ulysses_attention_sharded(
        mesh, *a, dp_axis=None, dropout_rate=0.4,
        dropout_seed=5))(q1, k1, v1))
    pairs_equal = [np.allclose(d1[:, a], d1[:, b])
                   for a in range(4) for b in range(a + 1, 4)]
    assert not any(pairs_equal), "head-tile dropout masks are correlated"
    g = jax.jit(jax.grad(lambda q: dropped(q).sum()))(q)
    assert np.isfinite(np.asarray(g)).all()
