"""``fused_attention``'s gradient reuses what its forward op computed
(ISSUE 31): the grad op takes the forward's ``Out`` — and, where the
Pallas dq/dkv kernels run, its row statistics ``Lse`` — and calls the
kernel's backward half, where the generic ``jax.vjp`` route ran the flash
forward kernel a second time.  On the CPU: ``impl="xla"`` and the Pallas
kernels in interpret mode, with ``PALLAS_BWD_MIN_L`` brought down to the
lengths a test can afford."""

import contextlib
import importlib

import jax
import numpy as np
import pytest

from paddle_tpu import fluid, parallel
from paddle_tpu.fluid.core import registry
from paddle_tpu.models import transformer as T
from paddle_tpu.observability.tracing import tracer

FA = importlib.import_module("paddle_tpu.kernels.flash_attention")
B, H, D = 2, 2, 8
MIN_L = 32          # stands in for the 1024 of production


@pytest.fixture(autouse=True)
def short_pallas_backward(monkeypatch):
    monkeypatch.setattr(FA, "PALLAS_BWD_MIN_L", MIN_L)


def attention_program(length, layout="bhld", causal=False, bias=False,
                      impl=None, dropout_rate=0.0, lk=None):
    """q, k, v (and a bias) as fed variables, ``sum(out * w)`` as the loss,
    and the gradient of each: -> (main, scope, feed, fetch names)."""
    lk = lk or length
    shape = (lambda l: [B, l, H, D]) if layout == "blhd" else \
        (lambda l: [B, H, l, D])
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    rng = np.random.RandomState(length + 7 * causal + 3 * bias)
    feed = {}

    def fed(name, dims):
        feed[name] = rng.randn(*dims).astype(np.float32)
        return fluid.layers.data(name, dims, "float32",
                                 append_batch_size=False)

    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, k, v = fed("q", shape(length)), fed("k", shape(lk)), \
            fed("v", shape(lk))
        leaves = [q, k, v]
        if bias:
            leaves.append(fed("bias", [B, 1, length, lk]))
        out = fluid.layers.fused_attention(
            q, k, v, bias=leaves[3] if bias else None, causal=causal,
            impl=impl, dropout_rate=dropout_rate, layout=layout)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(
            out, fed("w", shape(length))))
        grads = fluid.calc_gradient(loss, leaves)
    return main, fluid.Scope(), feed, [out.name] + [g.name for g in grads]


def run(main, scope, feed, fetch, mode="train"):
    with fluid.scope_guard(scope):
        return fluid.Executor(fluid.CPUPlace()).run(
            main, feed=feed, fetch_list=fetch, mode=mode)


def attn_grad_notes():
    return [e["args"] for e in tracer().events(name="lowering/attn_grad")]


@contextlib.contextmanager
def counted(monkeypatch, name):
    """Every trace of ``flash_attention.<name>``, as the list of the
    ``need_lse`` / keyword arguments it was given."""
    calls, real = [], getattr(FA, name)

    def wrapper(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    with monkeypatch.context() as m:
        m.setattr(FA, name, wrapper)
        yield calls


# -- (a) the direct route's gradients are the generic route's ----------------

@pytest.mark.parametrize("layout", ["bhld", "blhd"])
@pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("impl, length", [
    ("xla", MIN_L // 2), ("pallas_interpret", MIN_L // 2),
    ("pallas_interpret", MIN_L)])
def test_direct_gradients_equal_the_vjp_routes(impl, length, causal, bias,
                                               layout, monkeypatch):
    """Same program, same seed (so the same in-kernel dropout mask), the
    grad op lowered by its own emitter and then by ``jax.vjp`` over the
    forward emitter (the emitter taken out of the registry, which is all
    that preempts the generic road)."""
    args = dict(layout=layout, causal=causal, bias=bias, impl=impl,
                dropout_rate=0.25)
    tracer().clear()
    direct = run(*attention_program(length, **args))
    pallas_bwd = impl != "xla" and not bias and length >= MIN_L
    assert attn_grad_notes() == [{"route": "direct", "lse": pallas_bwd}]
    monkeypatch.delitem(registry._REGISTRY, "fused_attention_grad")
    generic = run(*attention_program(length, **args))
    assert len(direct) == (5 if bias else 4)
    for got, want in zip(direct, generic):
        assert np.abs(np.asarray(want)).max() > 1e-3
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_padded_lengths_keep_the_generic_route():
    """Lengths the kernel pads to a block multiple (1100 at blocks of 256
    in production; here 300) are not the direct route's: the grad op
    lowers through ``jax.vjp`` and says so."""
    main, scope, feed, fetch = attention_program(300, impl="xla")
    tracer().clear()
    out = run(main, scope, feed, fetch)
    assert attn_grad_notes() == [{"route": "vjp", "lse": False}]
    assert all(np.isfinite(np.asarray(o)).all() for o in out)


# -- (b) one forward kernel call per attention op in a training step ---------

def tiny_transformer(seq, minimize=True, seq_parallel=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        cost, _, _ = T.transformer(
            src_vocab_size=32, trg_vocab_size=32, max_length=seq + 1,
            n_layer=1, n_head=H, d_key=D, d_value=D, d_model=H * D,
            d_inner_hid=32, dropout_rate=0.0, src_seq_len=seq,
            trg_seq_len=seq, fused=True, materialize_attn_bias=False,
            seq_parallel=seq_parallel)
        test_prog = main.clone(for_test=True)
        if minimize:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    rng = np.random.RandomState(1)
    feed = {n: rng.randint(0, 32, (B, seq)).astype(np.int32)
            for n in ("src_word", "trg_word", "lbl_word")}
    feed.update({n: np.tile(np.arange(seq, dtype=np.int32), (B, 1))
                 for n in ("src_pos", "trg_pos")})
    feed["lbl_weight"] = np.ones((B, seq), np.float32)
    return main, startup, test_prog, cost, feed


@pytest.mark.parametrize("seq", [MIN_L // 2, MIN_L],
                         ids=["xla-backward", "pallas-backward"])
def test_training_step_runs_each_attention_forward_once(seq, monkeypatch):
    """Three attention ops (encoder self, decoder self, cross): three
    traces of the forward kernel in the lowered training step — the
    generic route made six — each asking for the statistics exactly where
    the Pallas backward reads them; the compile span carries the count."""
    monkeypatch.setattr(FA, "default_impl", lambda: "pallas_interpret")
    main, startup, _, cost, feed = tiny_transformer(seq)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    tracer().clear()
    with fluid.scope_guard(scope), counted(monkeypatch,
                                           "_pallas_forward") as fwd, \
            counted(monkeypatch, "_pallas_backward") as bwd:
        exe.run(startup)
        first = float(exe.run(main, feed=feed, fetch_list=[cost])[0])
        for _ in range(3):
            last = float(exe.run(main, feed=feed, fetch_list=[cost])[0])
    pallas_bwd = seq >= MIN_L
    assert [c["need_lse"] for c in fwd] == [pallas_bwd] * 3
    assert len(bwd) == (3 if pallas_bwd else 0)
    assert attn_grad_notes() == [{"route": "direct", "lse": pallas_bwd}] * 3
    compiles = [e for e in tracer().events(name="executor/compile")
                if e["args"]["mode"] == "train"
                and e["args"]["attn_grad_direct"]]
    assert len(compiles) == 1
    assert compiles[0]["args"]["attn_grad_direct"] == 3
    assert compiles[0]["args"]["attn_grad_vjp"] == 0
    notes = tracer().events(name="lowering/attn_grad")
    assert {e["parent"] for e in notes} == {compiles[0]["id"]}
    assert last < first                          # and it trains


# -- (c) programs without a gradient keep the primal-only call --------------

@pytest.mark.parametrize("which", ["inference", "for_test_clone"])
def test_programs_without_gradients_write_no_statistics(which, monkeypatch):
    monkeypatch.setattr(FA, "default_impl", lambda: "pallas_interpret")
    main, startup, test_prog, cost, feed = tiny_transformer(
        MIN_L, minimize=which == "for_test_clone")
    prog, mode = (main, "infer") if which == "inference" else \
        (test_prog, "train")
    attn = [op for op in prog.global_block().ops
            if op.type == "fused_attention"]
    assert len(attn) == 3 and not any(op.output("Lse") for op in attn)
    assert not any(n.endswith("@LSE") for n in prog.global_block().vars)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope), counted(monkeypatch,
                                           "_pallas_forward") as fwd, \
            counted(monkeypatch, "_flash_fwd") as with_residuals:
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[cost], mode=mode)
    assert [c["need_lse"] for c in fwd] == [False] * 3
    assert not with_residuals
    if which == "for_test_clone":       # the training program does have it
        assert sum(bool(op.output("Lse")) for op in main.global_block().ops
                   if op.type == "fused_attention") == 3


def test_a_training_program_in_infer_mode_still_lowers():
    """The slot is there, the mode is not ``train``: no statistics are
    computed, the grad op takes the generic route."""
    main, scope, feed, fetch = attention_program(MIN_L,
                                                 impl="pallas_interpret")
    assert main.global_block().ops[0].output("Lse")
    tracer().clear()
    got = run(main, scope, feed, fetch, mode="infer")
    assert attn_grad_notes() == [{"route": "vjp", "lse": False}]
    want = run(*attention_program(MIN_L, impl="pallas_interpret"))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


# -- (d) the statistics are held compactly -----------------------------------

@pytest.mark.parametrize("layout", ["bhld", "blhd"])
def test_lse_variable_is_one_float_a_row(layout):
    main, scope, feed, fetch = attention_program(
        MIN_L, layout=layout, causal=True, impl="pallas_interpret")
    fwd = main.global_block().ops[0]
    assert fwd.type == "fused_attention"
    lse_var = main.global_block().var(fwd.output("Lse")[0])
    assert lse_var.shape == (B * H, MIN_L) and lse_var.dtype == "float32"
    grad_op = next(op for op in main.global_block().ops
                   if op.type == "fused_attention_grad")
    assert grad_op.input("Lse") == fwd.output("Lse")
    assert grad_op.input("Out") == fwd.output("Out")
    lse, = run(main, scope, feed, [lse_var.name])
    assert lse.shape == (B * H, MIN_L)          # not (B * H, MIN_L, 128)
    q, k = feed["q"], feed["k"]
    if layout == "blhd":
        q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    s = np.where(np.tril(np.ones((MIN_L, MIN_L), bool)), s, -np.inf)
    want = np.log(np.exp(s).sum(-1)).reshape(B * H, MIN_L)
    np.testing.assert_allclose(lse, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("why, kwargs", [
    ("short", dict(length=MIN_L // 2)),
    ("bias", dict(length=MIN_L, bias=True)),
    ("padded", dict(length=MIN_L + 8, lk=300))])
def test_no_lse_slot_where_the_pallas_backward_will_not_run(why, kwargs):
    main, _, _, _ = attention_program(**kwargs)
    fwd = main.global_block().ops[0]
    assert fwd.type == "fused_attention" and not fwd.output("Lse")
    grad_op = next(op for op in main.global_block().ops
                   if op.type == "fused_attention_grad")
    assert grad_op.input("Out") == fwd.output("Out")
    assert not grad_op.input("Lse")


def test_program_structure_does_not_depend_on_the_build_host():
    """With no ``impl`` attribute the slot is added as for a TPU; on this
    CPU the lowering leaves it unused (NaN, dead) and the gradient is the
    XLA route's own."""
    main, scope, feed, fetch = attention_program(MIN_L)
    fwd = main.global_block().ops[0]
    assert fwd.output("Lse")
    tracer().clear()
    out = run(main, scope, feed, fetch + fwd.output("Lse"))
    assert attn_grad_notes() == [{"route": "direct", "lse": False}]
    assert np.isnan(out[-1]).all() and out[-1].shape == (B * H, MIN_L)
    assert all(np.isfinite(o).all() for o in out[:-1])
    report = main.analyze(fetch_list=fetch)
    assert not report.errors(), report.errors()


# -- (e) under a mesh the generic route still serves -------------------------

@pytest.mark.parametrize("axes, seq_parallel", [
    ({"dp": 2}, False), ({"dp": 2, "sp": 2}, True)],
    ids=["data-parallel", "sequence-parallel"])
def test_under_a_mesh_the_grad_op_falls_back_to_vjp(axes, seq_parallel):
    n = int(np.prod(list(axes.values())))
    mesh = parallel.make_mesh(axes, jax.devices()[:n])
    main, startup, _, cost, feed = tiny_transformer(
        MIN_L, seq_parallel=seq_parallel)
    assert sum(bool(op.output("Lse")) for op in main.global_block().ops
               if op.type == "fused_attention") == 3
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    tracer().clear()
    with parallel.mesh_guard(mesh), fluid.scope_guard(scope):
        exe.run(startup)
        losses = [float(exe.run(main, feed=feed, fetch_list=[cost])[0])
                  for _ in range(3)]
    assert attn_grad_notes() == [{"route": "vjp", "lse": False}] * 3
    assert losses[-1] < losses[0]
    single = fluid.Scope()
    with fluid.scope_guard(single):
        exe.run(startup)
        want = [float(exe.run(main, feed=feed, fetch_list=[cost])[0])
                for _ in range(3)]
    np.testing.assert_allclose(losses, want, rtol=2e-4, atol=2e-4)
