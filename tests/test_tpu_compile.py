"""Compiles for a described TPU v5e, without the chip (ISSUE 26): the
serve path's Mosaic kernel and the donated unified step at the served
widths (8 heads of 64, pages of 16 tokens, chunks of 32), through the
TPU compiler installed here.  What interpret mode and the CPU backend
cannot show: that Mosaic accepts the kernel's lane slices for every pool
dtype, and that the chip's compiler updates the token-major pool in
place.  Nothing runs and nothing is timed.  All such compiles live in
this one file: only one process may hold the TPU library, so the
topology is described inside a fixture, after collection."""

import os
import sys

import numpy as np
import pytest
from conftest import hlo_results_of_size

from paddle_tpu import fluid
from paddle_tpu.serving import PagedTransformerGenerator

H, D, PS, CHUNK, NL = 8, 64, 16, 32, 2


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("c", [1, CHUNK])
def test_ragged_kernel_compiles_for_v5e(kv_dtype, c, one_chip):
    """One page is one (1, page, H*D) block, cut into 128-lane groups of
    two heads each: Mosaic takes that for 4-, 2- and 1-byte pools, for a
    decode step's single query and for a prefill chunk."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import ragged_decode_attention

    b, p, rows = 8, 16, 64 * NL * 2
    scales = np.zeros((1, rows, PS), np.float32) \
        if kv_dtype == "int8" else None

    def call(q, pool, table, lengths, base, scales):
        return ragged_decode_attention(q, pool, table, lengths, base,
                                       layer=1, n_layer=NL, causal=True,
                                       impl="pallas", scales=scales)

    args = (np.zeros((b, c, H, D), np.float32),
            jnp.zeros((rows, PS, H * D), kv_dtype),
            np.zeros((b, p), np.int32), np.zeros(b, np.int32),
            np.zeros(b, np.int32), scales)
    hlo = jax.jit(call).lower(*_shapes(args, one_chip)).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("tower", [1, 8])
def test_unified_step_updates_the_pool_in_place_on_v5e(tower, one_chip,
                                                       monkeypatch):
    """The donated unified step, compiled by the chip's compiler at both
    widths its prefill tower takes at 8 lanes (ISSUE 29: one row, and a
    row a lane, beside 8 decode lanes): every
    ragged attention is a Mosaic call, the pool is aliased to its output,
    no copy or transpose of pool size remains, and the step's temporaries
    are a fraction of the pool (the head-major pool needed six times
    it).  The pool is 262 MB: one of a few MB the compiler may stage
    whole through faster memory, which is not what is guarded here."""
    import jax

    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.flash_attention"],
                        "default_impl", lambda: "pallas")
    gen = PagedTransformerGenerator(
        96, 96, n_layer=NL, n_head=H, d_key=D, d_value=D, d_model=H * D,
        d_inner_hid=256, max_length=65, src_len=64, max_out_len=64,
        page_size=PS, chunk_size=CHUNK, num_pages=2003, param_prefix="v5e",
        executor=fluid.Executor(fluid.CPUPlace()))
    gen.init_params(seed=1)
    gen.open_slots(8)
    assert gen.step_variants() == [1, 8]
    prog, _, next_ids, _ = gen._unified
    feed = gen._prefill_arrays(tower)
    feed.update(gen._decode_arrays())
    with fluid.scope_guard(gen.scope):
        feed, state, step = gen.exe._prepare_step(
            prog, feed, [next_ids], gen.scope, "infer")
    args = _shapes((feed, state, np.zeros(2, np.int32)), one_chip)
    compiled = gen.exe._jit_step(step).lower(*args).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3 * NL
    assert "input_output_alias" in hlo.splitlines()[0]
    n_elems = int(np.prod(gen._pool_shape))    # 2003 pages: no other match
    kinds = hlo_results_of_size(hlo[hlo.index("ENTRY "):], n_elems)
    assert kinds.pop("fusion") == 2 * 3 * NL, kinds    # the row scatters
    assert set(kinds) <= {"parameter", "bitcast"}, kinds
    pool_bytes = n_elems * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


@pytest.mark.parametrize("kind, hkv, ps, table, window", [
    ("global", 4, 256, 33, None), ("window", 8, 128, 4, 128)])
@pytest.mark.parametrize("b, c", [(64, 1), (16, 32)],
                         ids=["decode", "prefill-tile"])
def test_split_pool_kernel_compiles_for_v5e(kind, hkv, ps, table, window,
                                            b, c, one_chip):
    """The decoder-only model's attention (ISSUE 28) at its published
    widths, in bfloat16: 64 query heads on 4 or 8 KV heads, keys 192 wide
    (cut out of the token-major row in tile-aligned slices of 256) and
    values 128, a window with a sink over a ring of 4 pages, for a decode
    step's single query and for a prefill tile of 32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import ragged_decode_attention

    ring = window is not None

    def call(q, kp, vp, tbl, lengths, base, top, sink):
        return ragged_decode_attention(
            q, kp, tbl, lengths, base, layer=1, n_layer=2, impl="pallas",
            v_pool=vp, window=window, sink=sink if ring else None,
            ring_top=top if ring else None, kernel_name=f"paged_attn_{kind}")

    ints = np.zeros(b, np.int32)
    args = (jnp.zeros((b, c, 64, 192), jnp.bfloat16),
            jnp.zeros((64, ps, hkv * 192), jnp.bfloat16),
            jnp.zeros((64, ps, hkv * 128), jnp.bfloat16),
            np.zeros((b, table), np.int32), ints, ints, ints,
            np.zeros(64, np.float32))
    hlo = jax.jit(call).lower(*_shapes(args, one_chip)).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert f"paged_attn_{kind}" in hlo


@pytest.mark.parametrize("m, k, n", [(4608, 4096, 2048), (512, 2048, 4096)])
def test_grouped_expert_product_compiles_for_v5e(m, k, n, one_chip):
    """The expert layer's grouped product at its published widths: 8 held
    experts' stacked bfloat16 matrices, rows for the worst case of a step
    (576 tokens x 8) and of a decode-only one."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.grouped_matmul import grouped_matmul

    args = (jnp.zeros((m, k), jnp.bfloat16),
            jnp.zeros((8, k, n), jnp.bfloat16), np.zeros(8, np.int32))
    hlo = jax.jit(lambda a, w, g: grouped_matmul(a, w, g, impl="pallas")) \
        .lower(*_shapes(args, one_chip)).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("seq, backward_kernels", [(256, 0), (1024, 1)],
                         ids=["xla-backward", "pallas-backward"])
def test_training_step_calls_the_flash_forward_once_an_op_on_v5e(
        seq, backward_kernels, one_chip, monkeypatch):
    """The training step of a one-layer Transformer (heads of 64, bfloat16
    activations, no bias tensors: the train cells' attention), lowered
    and compiled for the chip at a length under and a length at
    ``PALLAS_BWD_MIN_L``: three attention ops, three ``flash_fwd`` Mosaic
    calls (ISSUE 31; the generic gradient made six), the dq and dkv
    kernels once each where they run, and the statistics between forward
    and backward one float a row."""
    import re

    import jax

    from paddle_tpu.models import transformer as T

    monkeypatch.setattr(sys.modules["paddle_tpu.kernels.flash_attention"],
                        "default_impl", lambda: "pallas")
    b, heads = 2, 2
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        cost, _, _ = T.transformer(
            src_vocab_size=96, trg_vocab_size=96, max_length=seq + 1,
            n_layer=1, n_head=heads, d_key=D, d_value=D, d_model=heads * D,
            d_inner_hid=256, dropout_rate=0.0, src_seq_len=seq,
            trg_seq_len=seq, fused=True, materialize_attn_bias=False,
            amp_dtype="bfloat16")
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(cost)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    feed = {n: np.zeros((b, seq), np.int32) for n in
            ("src_word", "trg_word", "lbl_word", "src_pos", "trg_pos")}
    feed["lbl_weight"] = np.ones((b, seq), np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed, state, step = exe._prepare_step(main, feed, [cost], scope,
                                              "train")
    args = _shapes((feed, state, np.zeros(2, np.int32)), one_chip)
    lowered = exe._jit_step(step).lower(*args)
    kernels = re.findall(r'kernel_name = "(\w+)"', lowered.as_text())
    assert kernels.count("flash_fwd") == 3
    assert kernels.count("flash_bwd_dq") == 3 * backward_kernels
    assert kernels.count("flash_bwd_dkv") == 3 * backward_kernels
    hlo = lowered.compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == len(kernels)
    entry = hlo[hlo.index("ENTRY "):]
    # what the forward kernel writes and the backward kernels read, and
    # what is held between them
    broadcast = hlo_results_of_size(entry, b * heads * seq * 128)
    compact = re.findall(rf"= f32\[{b * heads},{seq}\]\S* fusion\(", entry)
    assert broadcast.get("broadcast", 0) == 3 * backward_kernels
    assert len(compact) == 3 * backward_kernels


@pytest.mark.parametrize("b, c", [(128, 1), (8, 64)],
                         ids=["decode", "prefill-tile"])
def test_latent_kernel_compiles_for_v5e(b, c, one_chip):
    """The latent form of the split kernel (ISSUE 32) at the published
    widths, in bfloat16: 16 query heads on ONE 576-wide row a token whose
    leading 512 columns are the values, the pool in whole lane tiles
    (640), for 128 lanes' single query and for a prefill tile of 64.  The
    pool goes to the kernel as it lies (no copy, no temporary of its
    size: a 576-wide pool is laid out page-dimension-minor by the TPU and
    transposed whole before every call)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import ragged_decode_attention

    def call(q, pool, tbl, lengths, base):
        return ragged_decode_attention(
            q, pool, tbl, lengths, base, layer=1, n_layer=5, impl="pallas",
            latent_values=512, sm_scale=192 ** -0.5,
            kernel_name="paged_attn_latent")

    ints = np.zeros(b, np.int32)
    args = (jnp.zeros((b, c, 16, 576), jnp.bfloat16),
            jnp.zeros((65 * 5, 256, 640), jnp.bfloat16),
            np.zeros((b, 32), np.int32), ints, ints)
    compiled = jax.jit(call).lower(*_shapes(args, one_chip)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_attn_latent" in hlo
    pool_bytes = 65 * 5 * 256 * 640 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


# the split kernel's calls at the three served models' published shapes
# (ISSUE 44): (lanes, queries, query heads, key width, pool rows' width,
# value pool's width, page, table slots, layers, latent values, window,
# sink) -> slots a grid step
_SPLIT_CALLS = {
    "moonlight-latent-decode": ((128, 1, 16, 576, 640, None, 256, 32, 5,
                                 512, None, False), 8),
    "trinity-global-decode": ((64, 1, 32, 128, 512, 512, 256, 66, 2, None,
                               None, False), 4),
    "trinity-ring-decode": ((64, 1, 32, 128, 512, 512, 256, 10, 6, None,
                             2048, False), 4),
    "mimo-global-decode": ((64, 1, 64, 192, 768, 512, 256, 33, 2, None,
                            None, True), 4),
    "mimo-ring-decode": ((64, 1, 64, 192, 1536, 1024, 128, 4, 5, None,
                          128, True), 4),
    "trinity-global-tile": ((16, 64, 32, 128, 512, 512, 256, 66, 2, None,
                             None, False), 1),
    "trinity-ring-tile": ((16, 64, 32, 128, 512, 512, 256, 10, 6, None,
                           2048, False), 1),
    "mimo-global-tile": ((16, 32, 64, 192, 768, 512, 256, 33, 2, None,
                          None, True), 1),
    "mimo-ring-tile": ((16, 32, 64, 192, 1536, 1024, 128, 4, 5, None,
                        128, True), 1),
}


def _calls_on_the_pool(hlo, name, *pool_shapes):
    """How many Mosaic calls of the compiled step are named ``name``,
    each of which takes the bfloat16 pools of these shapes as they lie
    among its operands: what the benchmark's readers tell a paged
    attention call by."""
    calls = [ln.split("backend_config=")[0] for ln in hlo.splitlines()
             if "tpu_custom_call" in ln and f"%{name}." in ln.split("=")[0]]
    for ln in calls:
        operands = ln.split("operand_layout_constraints=")[1]
        for shape in pool_shapes:
            assert "bf16[%d,%d,%d]" % tuple(shape) in operands, (name, shape)
    return len(calls)


@pytest.mark.parametrize("call", list(_SPLIT_CALLS))
def test_split_kernel_walks_a_group_of_slots_on_the_v5e(call, one_chip):
    """ISSUE 44: a decode call of each served model takes the derived
    group of table slots a grid step (its pages copied by the kernel into
    ``SPLIT_VMEM_BYTES`` of fast memory: Mosaic refuses a kernel that
    asks for more), a prefill tile one slot; each is ONE Mosaic call that
    takes the pools as they lie (no copy, no temporary of their size)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import (ragged_decode_attention,
                                                    split_walk)

    (b, c, h, dk, kw, vw, ps, slots, nl, latent, window,
     sink), group = _SPLIT_CALLS[call]
    rows = 65 * nl
    ints = np.zeros(b, np.int32)
    pools = [jnp.zeros((rows, ps, kw), jnp.bfloat16)] + (
        [] if vw is None else [jnp.zeros((rows, ps, vw), jnp.bfloat16)])
    args = [jnp.zeros((b, c, h, dk), jnp.bfloat16),
            np.zeros((b, slots), np.int32), ints, ints, ints,
            jnp.zeros(h, jnp.float32), *pools]
    assert split_walk(args[0], pools[0], None if vw is None else pools[1],
                      args[1], latent) == (group, (b, -(-slots // group)))

    def kernel(q, tbl, lengths, base, top, sk, pool, v_pool=None):
        return ragged_decode_attention(
            q, pool, tbl, lengths, base, layer=1, n_layer=nl, impl="pallas",
            v_pool=v_pool, latent_values=latent, window=window,
            ring_top=top if window else None, sink=sk if sink else None,
            kernel_name="paged_attn_x")

    compiled = jax.jit(kernel).lower(*_shapes(args, one_chip)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert _calls_on_the_pool(hlo, "paged_attn_x",
                              *(p.shape for p in pools)) == 1
    pool_bytes = rows * ps * kw * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4


def test_latent_serve_step_compiles_for_v5e(one_chip):
    """The donated serve step of ``moonlight-16b-a3b-l5`` (ISSUE 32) at
    its published widths, 128 lanes and two chunks of 256, compiled by
    the chip's compiler from shapes alone: 10 latent attention calls and
    12 grouped expert products are Mosaic calls, the one pool is aliased
    to its output and updated in place (five row scatters, no copy or
    transpose of its size), and the step's temporaries are a fraction of
    it."""
    import json

    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.serving import PagedLMGenerator
    from perfbench.families import deepseek_v3 as fam

    with open("perfbench/configs/moonlight-16b-a3b-l5.json",
              encoding="utf-8") as f:
        cfg = json.load(f)
    conf = dict(fam.serving(cfg)["manifest"]["config"], attn_impl="pallas",
                num_pages=161)
    gen = PagedLMGenerator(executor=fluid.Executor(fluid.CPUPlace()), **conf)
    gen.open_slots(conf["lanes"])
    prog, _, next_ids, _, loads = gen._steps_built[2]
    feed, _ = gen._feed([], 2)
    fetch = [next_ids.name, loads.name]
    _, _, _, state_in, state_out = gen.exe._classified(
        gen.exe._program_key(prog), feed, fetch, prog.desc.global_block())
    step = build_step_fn(prog.desc, 0, list(feed), state_in, state_out,
                         fetch, "infer")
    shapes = gen.builder.param_shapes(gen.model, gen.prefix)
    dtypes = gen.param_dtypes()
    pool = gen.layout["groups"]["global"]
    assert set(state_in) == set(shapes) | {pool["k"]}

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    state = {n: described(shapes[n], dtypes[n]) for n in shapes}
    state[pool["k"]] = described(pool["k_shape"], pool["dtype"])
    compiled = gen.exe._jit_step(step).lower(
        _shapes(feed, one_chip), state,
        described((2,), np.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 5 * 2 + 4 * 3
    assert hlo.count("paged_attn_latent") >= 10
    assert "input_output_alias" in hlo.splitlines()[0]
    assert ("attn_latent", {"form": "absorbed", "tile": 64, "row": 640,
                            "values": 512}) in step.noted
    # ISSUE 44: the walk each of the ten calls makes, once a compile: 128
    # decode lanes take 8 of their 32 slots a grid step, the two chunks'
    # 8 tiles of 64 queries one (a page a step, as before)
    walks = [a for what, a in step.noted if what == "attn_split"]
    assert walks == 5 * [
        {"kernel": "paged_attn_latent", "queries": 1, "slots": 32,
         "slots_per_step": 8, "grid_steps": 128 * 4},
        {"kernel": "paged_attn_latent", "queries": 64, "slots": 32,
         "slots_per_step": 1, "grid_steps": 8 * 32}]
    # each still ONE Mosaic call with the whole pool among its operands
    assert _calls_on_the_pool(hlo, "paged_attn_latent",
                              pool["k_shape"]) == 10
    n_elems = int(np.prod(pool["k_shape"]))    # 161 pages: no other match
    kinds = hlo_results_of_size(hlo[hlo.index("ENTRY "):], n_elems)
    assert kinds.pop("fusion") == 5, kinds              # the row scatters
    assert set(kinds) <= {"parameter", "bitcast"}, kinds
    assert compiled.memory_analysis().temp_size_in_bytes < n_elems * 2 / 4


def test_afmoe_serve_step_compiles_for_v5e(one_chip):
    """The donated serve step of ``trinity-mini-ep8-l8`` (ISSUE 42) at its
    published widths, 64 lanes and a chunk of 256 in tiles of 64 queries,
    compiled by the chip's compiler from shapes alone: 16 paged-attention
    calls (32 query heads on 4 KV heads, keys and values 128 wide; a
    global table of 66 slots, a window ring of 10 over a window of 2048)
    and 18 grouped expert products are Mosaic calls, QK-norm and the
    output gate are in the step under their names, both pool pairs are
    aliased to their outputs, and the step's temporaries are a fraction of
    the pools."""
    import json

    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.serving import PagedLMGenerator
    from perfbench.families import afmoe as fam

    with open("perfbench/configs/trinity-mini-ep8-l8.json",
              encoding="utf-8") as f:
        cfg = json.load(f)
    conf = dict(fam.serving(cfg)["manifest"]["config"], attn_impl="pallas",
                num_pages=67, window_pages=21)
    gen = PagedLMGenerator(executor=fluid.Executor(fluid.CPUPlace()), **conf)
    gen.open_slots(conf["lanes"])
    groups = gen.layout["groups"]
    assert gen.tile == 64
    assert (groups["global"]["table"], groups["window"]["table"],
            groups["window"]["decode_pages"]) == (66, 10, 9)
    prog, _, next_ids, _, loads = gen._steps_built[1]
    feed, _ = gen._feed([], 1)
    fetch = [next_ids.name, loads.name]
    _, _, _, state_in, state_out = gen.exe._classified(
        gen.exe._program_key(prog), feed, fetch, prog.desc.global_block())
    step = build_step_fn(prog.desc, 0, list(feed), state_in, state_out,
                         fetch, "infer")
    shapes = gen.builder.param_shapes(gen.model, gen.prefix)
    dtypes = gen.param_dtypes()

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    state = {n: described(shapes[n], dtypes[n]) for n in shapes}
    # the pools at the rows the configuration gives them
    served = fam.pool_shapes(cfg)
    for kind, g in groups.items():
        for p in ("k", "v"):
            state[g[p]] = described(served[kind], g["dtype"])
    assert set(state_in) == set(state)
    compiled = gen.exe._jit_step(step).lower(
        _shapes(feed, one_chip), state,
        described((2,), np.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 8 * 2 + 6 * 3
    # ISSUE 44: decode rows walk 4 slots a grid step (17 groups of the 66
    # global slots, 3 of the ring's 10), a chunk's 4 tiles a slot a step
    walks = {(a["kernel"], a["queries"]): a
             for what, a in step.noted if what == "attn_split"}
    assert {k: (a["slots"], a["slots_per_step"], a["grid_steps"])
            for k, a in walks.items()} == {
        ("paged_attn_global", 1): (66, 4, 64 * 17),
        ("paged_attn_window", 1): (10, 4, 64 * 3),
        ("paged_attn_global", 64): (66, 1, 4 * 66),
        ("paged_attn_window", 64): (10, 1, 4 * 10)}
    # each still ONE Mosaic call with its group's pool pair as operands
    for kind, calls in (("global", 2 * 2), ("window", 6 * 2)):
        assert _calls_on_the_pool(hlo, "paged_attn_" + kind, served[kind],
                                  served[kind]) == calls
    for name in ("paged_attn_global", "paged_attn_window", "attn/qk_norm",
                 "attn/gate", "ffn/shared", "ffn/dense", "moe/route",
                 "moe/experts"):
        assert name in hlo, name
    assert "input_output_alias" in hlo.splitlines()[0]
    pool_bytes = 2 * 2 * sum(int(np.prod(s)) for s in served.values())
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes / 16
