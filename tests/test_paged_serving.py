"""Paged-KV serving tests (ISSUE 6): ragged paged attention ops/kernel,
token-for-token greedy and score-for-score beam parity against the PR 5
dense-cache decoder, chunked-prefill interleaving in one dispatch,
copy-on-write prefix sharing, page-refcount invariants under random
admit/retire interleavings, page-aware admission (more in-flight than
dense under the same HBM budget, reject-with-error on infeasible
prompts), and the engine's true-vs-padded accounting satellite."""

import numpy as np
import pytest
from conftest import hlo_results_of_size

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                InferenceEngine, PagedTransformerGenerator,
                                PageAllocator, PoolCapacityError,
                                TransformerGenerator, copy_weights)
from paddle_tpu.serving.decoder import pack_sources
from paddle_tpu.serving.paged_decoder import TOWER_FEEDS, tower_widths
from paddle_tpu.serving.paging import chunk_hashes

V, NL, NH, DK, DM, DI = 24, 2, 2, 4, 16, 32
SRC, OUT, PS, CHUNK = 8, 8, 4, 4


@pytest.fixture(scope="module")
def paged_pair():
    """A paged generator and the PR 5 dense-cache decoder sharing one
    randomly-initialized scope.  The dense decoder runs with
    causal-encoder feeds — the same math the paged path computes
    chunk-by-chunk — making it the differential parity baseline."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    kw = dict(n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
              d_inner_hid=DI, max_length=64, src_len=SRC, scope=scope,
              executor=exe, param_prefix="tfp")
    dense = TransformerGenerator(V, V, max_out_len=OUT,
                                 causal_encoder=True, **kw)
    paged = PagedTransformerGenerator(V, V, max_out_len=OUT, page_size=PS,
                                      chunk_size=CHUNK, num_pages=64, **kw)
    dense.init_params(seed=7)
    return paged, dense


def _sources(seed=0, n=4):
    rng = np.random.RandomState(seed)
    seqs = [rng.randint(2, V, rng.randint(3, SRC + 1)) for _ in range(n)]
    return seqs, pack_sources(seqs, bucket=4)


# -- ops / kernel -------------------------------------------------------------

def test_paged_cache_write_and_page_copy(fresh_programs):
    """paged_cache_write lands each token's K/V at its (page, offset)
    rows for the right layer; paged_page_copy moves whole logical pages
    and src==dst encodes a no-op."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import paged_kv_rows

    main, startup, scope = fresh_programs
    H, D, NPAGES, L = 2, 3, 4, 2
    pool_shape = (NPAGES * L * 2, PS, H * D)
    pool = main.global_block().create_var(
        name="pool", shape=list(pool_shape), dtype="float32",
        persistable=True)
    k = layers.data("k", [1, H, D], "float32")
    v = layers.data("v", [1, H, D], "float32")
    pages = layers.data("pages", [1], "int32")
    offs = layers.data("offs", [1], "int32")
    layers.paged_cache_write(pool, k, v, pages, offs, layer=1, n_layer=L)
    exe = fluid.Executor(fluid.CPUPlace())
    scope.set_var("pool", jnp.zeros(pool_shape))
    rng = np.random.RandomState(0)
    kv = rng.randn(2, 1, H, D).astype(np.float32)
    vv = rng.randn(2, 1, H, D).astype(np.float32)
    pg = np.array([[1], [3]], np.int32)
    of = np.array([[2], [0]], np.int32)
    exe.run(main, feed={"k": kv, "v": vv, "pages": pg, "offs": of},
            fetch_list=["pool"])
    got = np.asarray(scope.find_var("pool"))
    k_rows, v_rows = paged_kv_rows(pg, 1, L)
    for b in range(2):
        np.testing.assert_array_equal(
            got[int(k_rows[b, 0]), int(of[b, 0])], kv[b, 0].reshape(-1))
        np.testing.assert_array_equal(
            got[int(v_rows[b, 0]), int(of[b, 0])], vv[b, 0].reshape(-1))
    assert np.count_nonzero(got) == 2 * 2 * H * D  # nothing else written

    # page copy: dst page 2 <- page 1, lane 1 no-op (src == dst == 0)
    main2 = fluid.Program()
    with fluid.program_guard(main2, fluid.Program()), \
            fluid.unique_name.guard():
        pool2 = main2.global_block().create_var(
            name="pool", shape=list(pool_shape), dtype="float32",
            persistable=True)
        src = layers.data("src", [], "int32")
        dst = layers.data("dst", [], "int32")
        layers.paged_page_copy(pool2, src, dst, n_layer=L)
    before = got.copy()
    exe.run(main2, feed={"src": np.array([1, 0], np.int32),
                         "dst": np.array([2, 0], np.int32)},
            fetch_list=["pool"])
    after = np.asarray(scope.find_var("pool"))
    rows = np.arange(2 * L)
    np.testing.assert_array_equal(after[2 * 2 * L + rows],
                                  before[1 * 2 * L + rows])
    np.testing.assert_array_equal(after[:2 * 2 * L],
                                  before[:2 * 2 * L])


def test_ragged_attention_matches_masked_reference(fresh_programs):
    """ragged_decode_attention (layer op, XLA path) == dense gather +
    per-row causally/length-masked softmax attention."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import paged_kv_rows

    main, startup, scope = fresh_programs
    H, D, L, NPAGES, P, C = 2, 4, 2, 6, 2, 2
    pool_shape = (NPAGES * L * 2, PS, H * D)
    rng = np.random.RandomState(1)
    pool_np = rng.randn(*pool_shape).astype(np.float32)
    pool = main.global_block().create_var(
        name="pool", shape=list(pool_shape), dtype="float32",
        persistable=True)
    q = layers.data("q", [C, H, D], "float32")
    tbl = layers.data("tbl", [P], "int32")
    ln = layers.data("ln", [], "int32")
    qb = layers.data("qb", [], "int32")
    out = layers.ragged_decode_attention(q, pool, tbl, ln, qb, layer=1,
                                         n_layer=L, causal=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope.set_var("pool", jnp.asarray(pool_np))
    B = 2
    qv = rng.randn(B, C, H, D).astype(np.float32)
    tv = np.array([[1, 2], [4, 5]], np.int32)
    lv = np.array([5, 7], np.int32)
    bv = np.array([3, 5], np.int32)
    got, = exe.run(main, feed={"q": qv, "tbl": tv, "ln": lv, "qb": bv},
                   fetch_list=[out])
    got = np.asarray(got)
    k_rows, v_rows = paged_kv_rows(tv, 1, L)
    scale = D ** -0.5
    for b in range(B):
        k = pool_np[np.asarray(k_rows)[b]].reshape(P * PS, H, D)
        v = pool_np[np.asarray(v_rows)[b]].reshape(P * PS, H, D)
        for c in range(C):
            n = min(int(lv[b]), int(bv[b]) + c + 1)
            s = np.einsum("hd,khd->hk", qv[b, c], k[:n]) * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want = np.einsum("hk,khd->hd", p, v[:n])
            np.testing.assert_allclose(got[b, c], want, rtol=1e-5,
                                       atol=1e-5)


def test_ragged_pallas_interpret_matches_xla():
    """The Pallas ragged kernel (scalar-prefetched block tables driving
    the page index maps) agrees with the XLA gather fallback, including
    dead lanes (lengths == 0 -> zero output on both paths)."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import ragged_decode_attention

    rng = np.random.RandomState(3)
    H, D, L, NPAGES, P, C, B = 2, 4, 3, 6, 3, 2, 3
    pool = jnp.asarray(rng.randn(NPAGES * L * 2, PS, H * D)
                       .astype(np.float32))
    q = jnp.asarray(rng.randn(B, C, H, D).astype(np.float32))
    tbl = jnp.asarray(rng.randint(0, NPAGES, (B, P)).astype(np.int32))
    lengths = jnp.asarray(np.array([7, 0, 11], np.int32))
    base = jnp.asarray(np.array([5, 0, 9], np.int32))
    for causal in (True, False):
        a = ragged_decode_attention(q, pool, tbl, lengths, base, layer=2,
                                    n_layer=L, causal=causal, impl="xla")
        b = ragged_decode_attention(q, pool, tbl, lengths, base, layer=2,
                                    n_layer=L, causal=causal,
                                    impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        assert (np.asarray(a)[1] == 0).all()       # dead lane contract


@pytest.mark.parametrize("axes,int8", [({"dp": 4}, False),
                                       ({"batch": 2, "model": 2}, False),
                                       ({"batch": 2, "model": 2}, True)])
def test_ragged_sharded_matches_unsharded(axes, int8, fresh_programs):
    """On a mesh the Pallas ragged kernel runs inside a shard_map (a
    Mosaic call cannot be auto-partitioned): lanes split over the batch
    axis, heads — q's and the pool's — over the model axis, the int8
    scale sidecar replicated.  Same numbers as the unsharded call, both
    called directly and through the op under ``mesh_guard`` (where
    ``kernel_axes`` picks the axes)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import parallel
    from paddle_tpu.kernels.flash_attention import (
        ragged_decode_attention, ragged_decode_attention_sharded)

    rng = np.random.RandomState(5)
    H, D, L, NPAGES, P, C, B = 2, 4, 2, 9, 2, 2, 4
    R = NPAGES * L * 2
    scales = None
    if int8:
        pool_np = rng.randint(-127, 128, (R, PS, H * D)).astype(np.int8)
        scales = jnp.asarray(rng.rand(1, R, PS).astype(np.float32) + 0.5)
    else:
        pool_np = rng.randn(R, PS, H * D).astype(np.float32)
    q = rng.randn(B, C, H, D).astype(np.float32)
    tbl = rng.randint(1, NPAGES, (B, P)).astype(np.int32)
    lengths = np.array([7, 0, 8, 3], np.int32)
    base = np.array([5, 0, 6, 1], np.int32)
    kw = dict(layer=1, n_layer=L, causal=True, impl="pallas_interpret",
              scales=scales)
    want = np.asarray(ragged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool_np), jnp.asarray(tbl),
        jnp.asarray(lengths), jnp.asarray(base), **kw))

    mesh = parallel.make_mesh(axes, jax.devices()[:4])
    b_ax, h_ax = parallel.kernel_axes(mesh, batch=B, heads=H)
    assert (b_ax, h_ax) == (("dp", None) if "dp" in axes
                            else ("batch", "model"))
    got = jax.jit(lambda *a: ragged_decode_attention_sharded(
        mesh, *a, batch_axis=b_ax, head_axis=h_ax, **kw))(
        q, pool_np, tbl, lengths, base)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    if int8:
        return
    main, startup, scope = fresh_programs
    pool = main.global_block().create_var(
        name="pool", shape=list(pool_np.shape), dtype="float32",
        persistable=True)
    if h_ax:
        pool.set_sharding((None, None, h_ax))
    out = layers.ragged_decode_attention(
        layers.data("q", [C, H, D], "float32"), pool,
        layers.data("tbl", [P], "int32"), layers.data("ln", [], "int32"),
        layers.data("qb", [], "int32"), layer=1, n_layer=L, causal=True,
        impl="pallas_interpret")
    scope.set_var("pool", jnp.asarray(pool_np))
    exe = fluid.Executor(fluid.CPUPlace())
    with parallel.mesh_guard(mesh):
        via_op, = exe.run(main, feed={"q": q, "tbl": tbl, "ln": lengths,
                                      "qb": base}, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(via_op), want, rtol=1e-6,
                               atol=1e-6)


# -- parity vs the dense decoder ----------------------------------------------

def test_greedy_parity_token_for_token(paged_pair):
    """The paged decoder (chunked causal prefill + ragged paged decode)
    must emit EXACTLY the tokens the dense-cache decoder emits with
    causal-encoder feeds, on mixed-length prompts."""
    paged, dense = paged_pair
    _, (tok, lens) = _sources(0)
    g_dense = dense.greedy(tok, lens, max_new=OUT, stop_at_end=False)
    g_paged = paged.greedy(tok, lens, max_new=OUT, stop_at_end=False)
    np.testing.assert_array_equal(g_paged, g_dense)


def test_greedy_parity_with_early_stop(paged_pair):
    paged, dense = paged_pair
    _, (tok, lens) = _sources(4)
    g_dense = dense.greedy(tok, lens, max_new=OUT, stop_at_end=True)
    g_paged = paged.greedy(tok, lens, max_new=OUT, stop_at_end=True)
    np.testing.assert_array_equal(g_paged, g_dense)


def test_beam_parity_with_shared_pages(paged_pair):
    """Beam over paged caches: the host-side table reorder (refcounted
    page sharing + in-dispatch copy-on-write) must reproduce the dense
    path's in-graph batch_gather cache reorder — identical ids/parents
    every step, scores to float tolerance, same backtrace."""
    paged, dense = paged_pair
    W = 3
    _, (tok, lens) = _sources(2, n=2)
    cow0 = paged.cache_stats()["pages"]["cow_copies"]
    p_ids, p_scores, (pi, pscore, pp) = paged.beam(
        tok, lens, beam_size=W, max_new=OUT, return_trace=True)
    d_ids, d_scores, (di, ds, dp) = dense.beam(
        tok, lens, beam_size=W, max_new=OUT, return_trace=True)
    assert len(di) == len(pi)
    for t in range(len(di)):
        np.testing.assert_array_equal(pi[t], di[t])
        np.testing.assert_array_equal(pp[t], dp[t])
        np.testing.assert_allclose(pscore[t], ds[t], rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(p_ids), np.asarray(d_ids))
    np.testing.assert_allclose(p_scores, d_scores, rtol=1e-4, atol=1e-5)
    # parent lanes genuinely shared pages: reorders forced COW copies
    assert paged.cache_stats()["pages"]["cow_copies"] > cow0
    # and nothing leaked: every beam/self/prompt page went back
    assert paged.cache_stats()["pages"]["in_use"] == 0
    paged.alloc.check_invariants()


# -- chunked prefill / unified dispatch ---------------------------------------

def test_prefill_and_decode_interleave_in_one_dispatch(paged_pair):
    """A lane mid-prefill and a lane mid-decode advance in the SAME
    lane_step dispatch (the no-separate-prefill-program contract), and
    the interleaving compiles nothing new once warm."""
    paged, dense = paged_pair
    seqs, (tok, lens) = _sources(6, n=4)
    ref = dense.greedy(tok, lens, max_new=OUT, stop_at_end=False)
    paged.greedy(tok, lens, max_new=OUT, stop_at_end=False)  # warm B=4
    misses0 = paged.cache_stats()["executable"]["misses"]
    paged.open_slots(4)
    paged.admit_slot(0, seqs[0], max_new=OUT)
    # drive lane 0 through prefill into decode
    while paged._lanes[0].phase == "prefill":
        assert paged.lane_step() == {}
    got0 = []
    emitted = paged.lane_step()
    got0.append(emitted[0])
    # admit lane 1 (prompt > chunk so it needs >= 2 prefill steps)
    long_prompt = seqs[np.argmax([len(s) for s in seqs])]
    assert len(long_prompt) > CHUNK
    slot1 = 1
    paged.admit_slot(slot1, long_prompt, max_new=OUT)
    interleaved = 0
    while paged._lanes[slot1].phase == "prefill":
        emitted = paged.lane_step()       # ONE dispatch, both lanes
        if 0 in emitted:
            got0.append(emitted[0])
            interleaved += 1
    assert interleaved >= 1, "decode lane must advance during prefill"
    np.testing.assert_array_equal(
        got0, ref[0][:len(got0)])         # interleaving changed nothing
    for i in range(4):
        paged.clear_slot(i)
    assert paged.cache_stats()["executable"]["misses"] == misses0
    paged.alloc.check_invariants()


# -- the prefill tower's widths (ISSUE 29) ------------------------------------

LANES = 16
WIDTHS = tower_widths(LANES)          # (2, 16)


@pytest.fixture(scope="module")
def wide_pair():
    """A 16-lane paged generator (tower widths 2 and 16, all resolved by
    ``aot_warm``) beside the dense decoder, one scope.  No prefix cache:
    a second run of the same prompts has to prefill them again."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    kw = dict(n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
              d_inner_hid=DI, max_length=64, src_len=SRC, scope=scope,
              executor=exe, param_prefix="tfw")
    dense = TransformerGenerator(V, V, max_out_len=OUT,
                                 causal_encoder=True, **kw)
    paged = PagedTransformerGenerator(
        V, V, max_out_len=OUT, page_size=PS, chunk_size=CHUNK,
        num_pages=16 * LANES, prefix_sharing=False, **kw)
    dense.init_params(seed=11)
    paged.aot_warm(LANES)
    return paged, dense


def _bursts_peaking_at(width):
    """Admission bursts, one a step, whose prefilling lanes a step peak
    above the next smaller width and at most at ``width``."""
    below = max([w for w in WIDTHS if w < width], default=0)
    return [1, below + 1, 1] if below + 1 < width else [1, width, 1]


def _drive(gen, seqs, bursts, force_width=None):
    """Serve ``seqs`` on 16 lanes, admitting ``bursts`` lanes before
    successive steps; returns (tokens per request, most lanes that
    prefilled in one step)."""
    gen.open_slots(LANES)
    if force_width is not None:
        gen._widths = (force_width,)
    out = [[] for _ in seqs]
    bursts, admitted, peak = list(bursts), 0, 0
    while bursts or any(lane.phase in ("prefill", "decode")
                        for lane in gen._lanes):
        for _ in range(bursts.pop(0) if bursts else 0):
            gen.admit_slot(admitted, seqs[admitted], max_new=OUT)
            admitted += 1
        peak = max(peak, sum(lane.phase == "prefill"
                             for lane in gen._lanes))
        for slot, tok in gen.lane_step().items():
            out[slot].append(tok)
        for slot, lane in enumerate(gen._lanes):
            if lane.phase == "decode" and len(out[slot]) >= OUT:
                lane.phase = "hold"
    assert admitted == len(seqs)
    gen.open_slots(LANES)           # lanes cleared, the derived widths back
    return out, peak


@pytest.mark.parametrize("width", WIDTHS)
def test_tower_width_follows_prefilling_lanes_same_tokens(width,
                                                          wide_pair):
    """Staggered admissions that take the number of prefilling lanes up
    to ``width`` and back: the tower is fed at the smallest width that
    holds them, and every request gets token for token what the dense
    decoder gives it and what a run held to the full width gives it."""
    paged, dense = wide_pair
    bursts = _bursts_peaking_at(width)
    seqs, (tok, lens) = _sources(20 + width, n=sum(bursts))
    ref = dense.greedy(tok, lens, max_new=OUT, stop_at_end=False)
    before = paged.counters()["steps_by_width"]
    got, peak = _drive(paged, seqs, bursts)
    used = {w: n - before.get(w, 0)
            for w, n in paged.counters()["steps_by_width"].items()
            if n > before.get(w, 0)}
    assert max(used) == width and peak <= width, (used, peak)
    assert min(used) == WIDTHS[0], "an idle tower takes the least width"
    full, _ = _drive(paged, seqs, bursts, force_width=LANES)
    np.testing.assert_array_equal(np.asarray(got), ref)
    np.testing.assert_array_equal(np.asarray(full), ref)
    paged.alloc.check_invariants()


@pytest.mark.parametrize("width", WIDTHS)
def test_every_tower_width_is_warm_after_aot_warm(width, wide_pair):
    """``aot_warm`` resolved one executable per width: traffic that
    reaches ``width`` prefilling lanes a step, and every width below
    it, adds no executable-cache miss."""
    paged, _ = wide_pair
    assert paged.step_variants() == list(WIDTHS)
    misses0 = paged.cache_stats()["executable"]["misses"]
    for w in [w for w in WIDTHS if w <= width]:
        bursts = _bursts_peaking_at(w)
        seqs, _ = _sources(40 + w, n=sum(bursts))
        _drive(paged, seqs, bursts)
    assert paged.cache_stats()["executable"]["misses"] == misses0


@pytest.mark.parametrize("width", WIDTHS)
def test_engine_counters_count_tower_rows(width, wide_pair):
    """``counters()``: the live rows are the prompt tokens prefilled,
    the rows fed are width x chunk a step, and the steps by width sum
    to the steps."""
    paged, _ = wide_pair
    bursts = _bursts_peaking_at(width)
    seqs, _ = _sources(60 + width, n=sum(bursts))
    c0 = paged.counters()
    _drive(paged, seqs, bursts)
    c1 = paged.counters()
    assert c1["tower_rows_live"] - c0["tower_rows_live"] == \
        sum(len(s) for s in seqs)
    by_width = {w: c1["steps_by_width"].get(w, 0)
                - c0["steps_by_width"].get(w, 0) for w in WIDTHS}
    assert sum(by_width.values()) == c1["steps"] - c0["steps"] > 0
    assert sum(c1["steps_by_width"].values()) == c1["steps"]
    assert c1["tower_rows_fed"] - c0["tower_rows_fed"] == \
        sum(w * CHUNK * n for w, n in by_width.items())
    assert by_width[width] > 0


@pytest.mark.parametrize("lanes", [1, 4, 16, 64])
def test_bucket_set_is_one_signature_per_tower_width(lanes, wide_pair):
    """The closed set at a lane count: one signature per width, the
    tower's eight feeds leading with the width and every other feed with
    the lane count; nothing else."""
    paged, _ = wide_pair
    widths = tower_widths(lanes)
    assert widths[-1] == lanes and widths[0] == max(1, lanes // 8)
    buckets = paged.bucket_set(lanes)
    assert len(buckets) == len(widths)
    for width, entry in zip(widths, buckets):
        assert entry["closed"] and entry["batch"] == lanes
        lead = {name: f["shape"][0] for name, f in entry["feeds"].items()}
        assert {n for n, d in lead.items() if d == width} >= \
            set(TOWER_FEEDS)
        assert all(d == lanes for n, d in lead.items()
                   if n not in TOWER_FEEDS)
        assert len(lead) == len(TOWER_FEEDS) + 9


def test_prefill_feed_rows_are_prefilling_lanes_in_slot_order(wide_pair):
    """The tower's feed is [width, chunk]: row i is the i-th prefilling
    lane in slot order, the padding rows write the trash page, and a
    width too small for the prefilling lanes is refused."""
    paged, _ = wide_pair
    seqs, _ = _sources(77, n=3)
    paged.open_slots(LANES)
    for slot, s in zip((9, 2, 5), seqs):
        paged.admit_slot(slot, s, max_new=OUT)
    feed = paged._prefill_arrays()
    assert feed["pf_word"].shape == (LANES, CHUNK)      # 3 lanes > 2
    for row, (slot, s) in enumerate(sorted(zip((9, 2, 5), seqs))):
        m = min(CHUNK, len(s))
        np.testing.assert_array_equal(feed["pf_word"][row, :m], s[:m])
        assert feed["pf_len"][row] == m
        assert paged._lanes[slot].pending_chunk == m
    assert (feed["enc_pages"][3:] == 0).all() and \
        (feed["pf_len"][3:] == 1).all()
    with pytest.raises(ValueError, match="cannot hold"):
        paged._prefill_arrays(width=2)
    paged.clear_slot(5)
    assert paged._prefill_arrays()["pf_word"].shape == (2, CHUNK)
    assert paged._step_feed()["pf_word"].shape == (LANES, CHUNK)
    for slot in (9, 2):
        paged.clear_slot(slot)


# -- prefix sharing -----------------------------------------------------------

def test_prefix_sharing_dedups_with_unchanged_outputs(paged_pair):
    """Two requests sharing a system-prompt prefix occupy the SAME
    physical pages (asserted via page tables + chunk refcounts) and
    decode exactly what a sharing-disabled generator decodes."""
    paged, dense = paged_pair
    rng = np.random.RandomState(11)
    system = rng.randint(2, V, 6)
    a = np.concatenate([system, [7, 9]])[:SRC]
    b = np.concatenate([system, [11, 3]])[:SRC]
    # seed the cache with a's chunks
    ga = paged.greedy(*pack_sources([a]), max_new=OUT, stop_at_end=False)
    paged.open_slots(2)
    paged.admit_slot(0, a, max_new=OUT)
    paged.admit_slot(1, b, max_new=OUT)
    l0, l1 = paged._lanes[0], paged._lanes[1]
    # a re-admitted: full prefix hit; b: shares the first chunk only
    assert l0.enc_table[0] == l1.enc_table[0]
    assert l0.cross_table[0] == l1.cross_table[0]
    assert l0.enc_table[1] != l1.enc_table[1]
    shared_hash = chunk_hashes(a, PS)[0]
    assert paged.alloc._chunks[shared_hash][2] == 2       # both lanes ref
    for i in (0, 1):
        paged.clear_slot(i)
    paged.alloc.check_invariants()
    # outputs: sharing-enabled == sharing-disabled == dense baseline
    st0 = paged.cache_stats()["pages"]
    both = paged.greedy(*pack_sources([a, b]), max_new=OUT,
                        stop_at_end=False)
    st1 = paged.cache_stats()["pages"]
    assert st1["prefix_hits"] > st0["prefix_hits"]
    np.testing.assert_array_equal(both[0], ga[0])
    ref = dense.greedy(*pack_sources([a, b]), max_new=OUT,
                       stop_at_end=False)
    np.testing.assert_array_equal(both, ref)


# -- allocator invariants -----------------------------------------------------

def test_allocator_random_interleavings_never_leak():
    """Property test: random interleavings of admit-like alloc/ref,
    prefix insert/hit, beam-like share/COW, and retire/free keep the
    free/held partition exact — no leaked page, no double free."""
    rng = np.random.RandomState(42)
    alloc = PageAllocator(num_pages=24, page_size=PS)
    live = []          # [(pages, chunk_hashes_reffed, inserted)]
    next_tok = [0]
    for step in range(400):
        op = rng.rand()
        try:
            if op < 0.45:          # admit: alloc pages, maybe share
                toks = rng.randint(0, 9, int(rng.randint(PS, 4 * PS)))
                hashes = chunk_hashes(toks, PS)
                hits = alloc.lookup_chain(hashes)
                pages = alloc.alloc(int(rng.randint(1, 4)))
                for h, _, _ in hits:
                    alloc.ref_chunk(h)
                live.append([pages, [h for h, _, _ in hits], []])
            elif op < 0.6 and live:     # beam-like page share + unshare
                ent = live[int(rng.randint(len(live)))]
                if ent[0]:
                    p = ent[0][int(rng.randint(len(ent[0])))]
                    alloc.ref(p)
                    alloc.unref(p)
            elif op < 0.75 and live:    # insert a computed chunk pair
                ent = live[int(rng.randint(len(live)))]
                if len(ent[0]) >= 2:
                    h = f"synthetic-{next_tok[0]}"
                    next_tok[0] += 1
                    if alloc.insert_chunk(h, ent[0][0], ent[0][1]):
                        ent[2].append(h)
                        del ent[0][:2]
            elif live:                  # retire
                pages, hashes, inserted = live.pop(
                    int(rng.randint(len(live))))
                for h in hashes + inserted:
                    alloc.unref_chunk(h)
                for p in pages:
                    alloc.unref(p)
        except PoolCapacityError:
            pass
        alloc.check_invariants()
    for pages, hashes, inserted in live:
        for h in hashes + inserted:
            alloc.unref_chunk(h)
        for p in pages:
            alloc.unref(p)
    alloc.check_invariants()
    st = alloc.stats()
    # everything released: cached chunks are evictable (still hittable)
    # and count as available capacity — nothing is leaked in-use
    assert st["in_use"] == 0
    assert st["free"] + st["evictable"] == st["total"]


def test_tiered_allocator_random_interleavings_bitwise():
    """Property test over the TIERED allocator (ISSUE 20): random
    interleavings of alloc/free, chunk cache/hit, demote (LRU spill to
    the host pool), promote (fetch back to fresh pages), and
    pressure-driven evictions keep ``check_invariants`` green at every
    step — and any chunk promoted back to HBM carries bitwise-identical
    bytes to what it held when it was first cached.  The pager is a
    host-side fake over a page->bytes shadow dict, so byte movement is
    EXACTLY what the allocator requested — no device needed."""
    rng = np.random.RandomState(4242)
    alloc = PageAllocator(num_pages=24, page_size=PS, host_pages=10)
    shadow = {}         # fake device pool: page -> row bytes
    golden = {}         # chunk hash -> bytes at insert time

    def download(pages):
        return {"kv": np.stack([shadow[p] for p in pages]),
                "scales": None}

    def upload(pages, payload):
        for i, p in enumerate(pages):
            shadow[p] = payload["kv"][i].copy()

    alloc.set_pager(download, upload, page_bytes=64)
    live = []           # [(pages, reffed_hashes, inserted_hashes)]
    uniq = [0]
    for step in range(450):
        op = rng.rand()
        try:
            if op < 0.35:          # admit: alloc + pin prefix hits
                toks = rng.randint(0, 9, int(rng.randint(PS, 4 * PS)))
                hits = alloc.lookup_chain(chunk_hashes(toks, PS))
                pages = alloc.alloc(int(rng.randint(1, 4)))
                for p in pages:    # "compute" writes fresh page bytes
                    shadow[p] = rng.randint(0, 256, 8).astype(np.uint8)
                for h, _, _ in hits:
                    alloc.ref_chunk(h)
                live.append([pages, [h for h, _, _ in hits], []])
            elif op < 0.5 and live:     # cache a computed chunk pair
                ent = live[int(rng.randint(len(live)))]
                if len(ent[0]) >= 2:
                    h = f"tier-{uniq[0]}"
                    uniq[0] += 1
                    enc, cross = ent[0][0], ent[0][1]
                    if alloc.insert_chunk(h, enc, cross):
                        golden[h] = np.stack(
                            [shadow[enc], shadow[cross]]).copy()
                        ent[2].append(h)
                        del ent[0][:2]
            elif op < 0.65:             # eager demote (watermark path)
                alloc.demote_one()
            elif op < 0.8:              # promote a random host chunk
                if alloc.host is not None and len(alloc.host):
                    h = list(alloc.host._entries)[
                        int(rng.randint(len(alloc.host)))]
                    if alloc.promote_chunk(h):
                        enc, cross, rc = alloc._chunks[h]
                        got = np.stack([shadow[enc], shadow[cross]])
                        np.testing.assert_array_equal(
                            got, golden[h],
                            err_msg=f"promoted chunk {h} lost bytes")
            elif live:                  # retire
                pages, hashes, inserted = live.pop(
                    int(rng.randint(len(live))))
                for h in hashes + inserted:
                    alloc.unref_chunk(h)
                for p in pages:
                    alloc.unref(p)
        except PoolCapacityError:
            pass
        alloc.check_invariants()
    for pages, hashes, inserted in live:
        for h in hashes + inserted:
            alloc.unref_chunk(h)
        for p in pages:
            alloc.unref(p)
    alloc.check_invariants()
    st = alloc.stats()
    assert st["in_use"] == 0
    assert st["free"] + st["evictable"] == st["total"]
    assert st["demotes"] > 0 and st["promotes"] > 0, \
        "seeded walk never exercised the tier"
    # every chunk still resident in EITHER tier matches its insert-time
    # bytes (host side stores the downloaded payload verbatim)
    for h, (enc, cross, _) in alloc._chunks.items():
        np.testing.assert_array_equal(
            np.stack([shadow[enc], shadow[cross]]), golden[h])
    if alloc.host is not None:
        for h, (payload, _) in alloc.host._entries.items():
            np.testing.assert_array_equal(payload["kv"], golden[h])


def test_admit_under_pressure_pins_hit_chunks():
    """Regression: admit_slot refs its prefix-cache hits BEFORE
    allocating fresh pages, so an allocation that must evict under pool
    pressure can never evict the hit it just counted (which raised
    KeyError from ref_chunk and leaked the fresh pages)."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    gen = PagedTransformerGenerator(
        V, V, n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
        d_inner_hid=DI, max_length=64, src_len=SRC, max_out_len=4,
        scope=scope, executor=exe, param_prefix="tfpin", page_size=PS,
        chunk_size=CHUNK, num_pages=12)
    gen.init_params(seed=2)
    rng = np.random.RandomState(21)
    a = rng.randint(2, V, PS)          # one FULL chunk -> cached
    d = rng.randint(2, V, PS)
    gen.greedy(*pack_sources([a]), max_new=2, stop_at_end=False)
    gen.greedy(*pack_sources([d]), max_new=2, stop_at_end=False)
    # both chunks sit refcount-0 on the evictable list (a is LRU-first);
    # drain the free list to zero with admissions that never step
    assert gen.alloc.stats()["free"] == 7
    gen.open_slots(5)
    gen.admit_slot(0, rng.randint(2, V, 2), max_new=4)      # 3 pages
    gen.admit_slot(1, rng.randint(2, V, 2), max_new=0)      # 2 pages
    gen.admit_slot(2, rng.randint(2, V, 2), max_new=0)      # 2 pages
    assert gen.alloc.stats()["free"] == 0
    # re-admitting a: prefix hit on the LRU-FIRST evictable chunk, plus
    # one fresh self page -> the alloc must evict; it must evict d's
    # chunk, never the pinned hit
    gen.admit_slot(3, a, max_new=4)
    lane = gen._lanes[3]
    assert lane.hit_hashes == [chunk_hashes(a, PS)[0]]
    assert gen.alloc.stats()["evictions"] == 1       # d's chunk went
    assert gen.alloc.lookup_chain(chunk_hashes(d, PS), count=False) == []
    gen.alloc.check_invariants()
    for i in range(4):
        gen.clear_slot(i)
    gen.alloc.check_invariants()
    assert gen.alloc.stats()["in_use"] == 0


def test_allocator_double_free_and_exhaustion():
    alloc = PageAllocator(num_pages=4, page_size=PS)
    pages = alloc.alloc(3)
    with pytest.raises(PoolCapacityError):
        alloc.alloc(1)
    alloc.unref(pages[0])
    with pytest.raises(ValueError, match="double free"):
        alloc.unref(pages[0])
    # all-or-nothing alloc rolled back cleanly
    with pytest.raises(PoolCapacityError):
        alloc.alloc(2)
    assert alloc.available() == 1
    alloc.check_invariants()


# -- page-aware admission -----------------------------------------------------

def test_paged_admits_more_in_flight_than_dense_same_hbm(paged_pair):
    """Under the same simulated HBM budget and a mixed-length workload,
    page-granular admission holds strictly more concurrent requests
    than dense worst-case per-slot reservation."""
    paged, dense = paged_pair
    budget = 4 * dense.kv_bytes_per_slot()        # 4 dense slots' worth
    n_dense = budget // dense.kv_bytes_per_slot()
    scope = fluid.Scope()          # fresh pool sized to the budget
    exe = fluid.Executor(fluid.CPUPlace())
    gen = PagedTransformerGenerator(
        V, V, n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
        d_inner_hid=DI, max_length=64, src_len=SRC, max_out_len=OUT,
        scope=scope, executor=exe, param_prefix="tfcap", page_size=PS,
        chunk_size=CHUNK, num_pages=budget // paged.page_bytes)
    rng = np.random.RandomState(9)
    admitted = 0
    gen.open_slots(32)
    while admitted < 32:
        prompt = rng.randint(2, V, int(rng.randint(2, SRC // 2 + 1)))
        if not gen.can_admit(prompt, max_new=PS):
            break
        gen.admit_slot(admitted, prompt, max_new=PS)
        admitted += 1
    assert admitted > n_dense, (admitted, n_dense)
    st = gen.cache_stats()
    assert st["hbm"]["bytes_in_use"] <= budget
    assert st["hbm"]["bytes_per_active_slot"] < \
        st["hbm"]["dense_bytes_per_slot"]


def test_scheduler_paged_integrity_and_zero_recompiles(paged_pair):
    """Seeded mixed-length traffic through the paged scheduler: every
    request decodes exactly its own prompt's greedy tokens (admission,
    chunked prefill, backfill at ragged depths can't cross-contaminate),
    pages are freed at retire, and a second full round compiles
    NOTHING (including across chunked-prefill interleaving)."""
    paged, _ = paged_pair
    seqs, (tok, lens) = _sources(5, n=5)
    ref = paged.greedy(tok, lens, max_new=OUT, stop_at_end=False)
    ref_rows = {tuple(s.tolist()): ref[i].tolist()
                for i, s in enumerate(seqs)}
    rng = np.random.RandomState(9)
    sched = ContinuousBatchingScheduler(paged, n_slots=4,
                                        max_new_tokens=OUT)
    order = [seqs[int(rng.randint(len(seqs)))] for _ in range(9)]
    reqs = []
    it = iter(order)
    for burst in (3, 2, 3, 1):
        for _ in range(burst):
            reqs.append(sched.submit(next(it)))
        for _ in range(int(rng.randint(1, 5))):
            sched.step_once()
    sched.run_until_idle()
    assert all(r.done and r.error is None for r in reqs)
    for req, src in zip(reqs, order):
        want = ref_rows[tuple(np.asarray(src).tolist())]
        got = req.tokens
        assert got == want[:len(got)], (got, want)
        if len(got) < OUT:
            assert got[-1] == paged.end_id
    st = sched.stats()
    assert st["finished"] == len(order)
    assert st["queued"] == 0 and st["in_flight"] == 0
    assert paged.cache_stats()["pages"]["in_use"] == 0   # retire freed
    misses0 = paged.cache_stats()["executable"]["misses"]
    sched2 = ContinuousBatchingScheduler(paged, n_slots=4,
                                         max_new_tokens=OUT)
    for s in order[::-1]:
        sched2.submit(s)
    sched2.run_until_idle()
    assert paged.cache_stats()["executable"]["misses"] == misses0
    paged.alloc.check_invariants()


def test_scheduler_rejects_infeasible_prompt_seeded(paged_pair):
    """Satellite: a prompt whose pages can NEVER fit the pool rejects
    with PoolCapacityError at submit instead of hanging the queue; a
    feasible-but-currently-blocked prompt waits and is admitted once
    retirement frees pages."""
    paged, _ = paged_pair
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    # pool fits ONE worst-case request (2*2 prompt pages + 2 self pages)
    tiny = PagedTransformerGenerator(
        V, V, n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
        d_inner_hid=DI, max_length=64, src_len=SRC, max_out_len=OUT,
        scope=scope, executor=exe, param_prefix="tftiny", page_size=PS,
        chunk_size=CHUNK, num_pages=6, prefix_sharing=False)
    sched = ContinuousBatchingScheduler(tiny, n_slots=2,
                                        max_new_tokens=OUT)
    rng = np.random.RandomState(13)
    # a full-length request needs 2*2 prompt + 2 self pages = 6, but
    # only 5 of the 6 pool pages are usable (page 0 is trash)
    with pytest.raises(PoolCapacityError):
        sched.submit(rng.randint(2, V, SRC), max_new_tokens=OUT)
    # belt-and-braces: the admission-time guard also rejects (a request
    # that slipped past submit, e.g. queued before a pool resize)
    bad = sched.submit(rng.randint(2, V, 2), max_new_tokens=2)
    sched._queue[0].src = rng.randint(2, V, SRC)
    sched._queue[0].max_new_tokens = OUT
    sched.run_until_idle()
    assert bad.done and isinstance(bad.error, PoolCapacityError)
    assert tiny.cache_stats()["pages"]["in_use"] == 0


def test_scheduler_backpressure_waits_then_admits(paged_pair):
    """Two feasible requests that cannot fit TOGETHER: the second waits
    (no hang, no error) and admits as soon as the first retires."""
    paged, _ = paged_pair
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    tiny = PagedTransformerGenerator(
        V, V, n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
        d_inner_hid=DI, max_length=64, src_len=SRC, max_out_len=OUT,
        scope=scope, executor=exe, param_prefix="tfbp", page_size=PS,
        chunk_size=CHUNK, num_pages=8, prefix_sharing=False)
    tiny.init_params(seed=5)
    sched = ContinuousBatchingScheduler(tiny, n_slots=2,
                                        max_new_tokens=4)
    rng = np.random.RandomState(17)
    r1 = sched.submit(rng.randint(2, V, SRC), max_new_tokens=4)
    r2 = sched.submit(rng.randint(2, V, SRC), max_new_tokens=4)
    sched.step_once()
    assert r1.slot is not None and r2.slot is None     # r2 queued
    sched.run_until_idle()
    assert r1.done and r1.error is None
    assert r2.done and r2.error is None and len(r2.tokens) >= 1
    assert sched.stats()["peak_in_flight"] == 1


# -- engine padding accounting (satellite) ------------------------------------

def test_engine_padding_accounting_reports_true_vs_padded():
    """cache_stats()['padding'] exposes what bucketing really costs:
    true rows/tokens requested vs rows/tokens dispatched."""
    from paddle_tpu.fluid.core.lod import make_seq

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        w = fluid.layers.data("w", [1], "int64", lod_level=1)
        emb = fluid.layers.embedding(input=w, size=[V, 8])
        pooled = fluid.layers.sequence_pool(input=emb, pool_type="sum")
        y = fluid.layers.fc(input=pooled, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    infer = fluid.io.get_inference_program([y], main)
    eng = InferenceEngine(program=infer, feed_names=["w"], fetch_vars=[y],
                          scope=scope, executor=exe, batch_buckets=(4,),
                          time_bucket=8)
    lens = [3, 5]                      # 2 rows -> bucket 4; times -> 8
    rng = np.random.RandomState(0)
    eng.infer({"w": make_seq([rng.randint(0, V, n) for n in lens],
                             dtype=np.int64)})
    pad = eng.cache_stats()["padding"]
    assert pad["true_rows"] == 2 and pad["padded_rows"] == 4
    assert pad["true_tokens"] == 8 and pad["padded_tokens"] == 32
    assert pad["padded_row_fraction"] == 0.5
    assert pad["padded_token_fraction"] == 0.75
    # warmup dispatches stay invisible — the counters stay honest
    eng.warmup([{"w": make_seq([rng.randint(0, V, 4)], dtype=np.int64)}])
    assert eng.cache_stats()["padding"] == pad


# -- int8 quantized KV pages (ISSUE 7) ----------------------------------------

def _kv_pool_pair(kv_dtype, prefix):
    """A float32-pool and a ``kv_dtype``-pool paged generator sharing one
    set of trained weights (copied by name into the second generator's
    scope — the pool var name is shared, so the scopes must differ)."""
    exe = fluid.Executor(fluid.CPUPlace())
    kw = dict(n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
              d_inner_hid=DI, max_length=64, src_len=SRC, executor=exe,
              param_prefix=prefix, max_out_len=OUT, page_size=PS,
              chunk_size=CHUNK, num_pages=64)
    sa, sb = fluid.Scope(), fluid.Scope()
    fp = PagedTransformerGenerator(V, V, scope=sa, **kw)
    alt = PagedTransformerGenerator(V, V, scope=sb, kv_dtype=kv_dtype,
                                    **kw)
    fp.init_params(seed=7)
    copy_weights(sa, sb)
    return fp, alt


@pytest.fixture(scope="module")
def int8_pair():
    return _kv_pool_pair("int8", "tfq")


def test_quantized_paged_cache_write_roundtrip_and_scale_placement(
        fresh_programs):
    """quantized_paged_cache_write lands int8 bytes at the same
    (row, slot) paged_cache_write would, with one fp32 max-abs block
    scale per (token, layer, role) in the sidecar at that SAME
    (row, slot); dequantizing the pool recovers the written K/V within
    the symmetric-rounding bound scale/2.  quantized_paged_page_copy
    moves pool bytes and scales together (the COW contract)."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import paged_kv_rows

    main, startup, scope = fresh_programs
    H, D, NPAGES, L = 2, 3, 4, 2
    pool_shape = (NPAGES * L * 2, PS, H * D)
    scales_shape = (1, NPAGES * L * 2, PS)
    pool = main.global_block().create_var(
        name="pool", shape=list(pool_shape), dtype="int8",
        persistable=True)
    scales = main.global_block().create_var(
        name="scales", shape=list(scales_shape), dtype="float32",
        persistable=True)
    k = layers.data("k", [1, H, D], "float32")
    v = layers.data("v", [1, H, D], "float32")
    pages = layers.data("pages", [1], "int32")
    offs = layers.data("offs", [1], "int32")
    layers.quantized_paged_cache_write(pool, scales, k, v, pages, offs,
                                       layer=1, n_layer=L)
    exe = fluid.Executor(fluid.CPUPlace())
    scope.set_var("pool", jnp.zeros(pool_shape, jnp.int8))
    scope.set_var("scales", jnp.zeros(scales_shape, jnp.float32))
    rng = np.random.RandomState(0)
    kv = (rng.randn(2, 1, H, D) * 3).astype(np.float32)
    vv = (rng.randn(2, 1, H, D) * 0.2).astype(np.float32)
    pg = np.array([[1], [3]], np.int32)
    of = np.array([[2], [0]], np.int32)
    exe.run(main, feed={"k": kv, "v": vv, "pages": pg, "offs": of},
            fetch_list=["pool"])
    got = np.asarray(scope.find_var("pool"))
    got_sc = np.asarray(scope.find_var("scales"))
    assert got.dtype == np.int8 and got_sc.dtype == np.float32
    k_rows, v_rows = paged_kv_rows(pg, 1, L)
    for b in range(2):
        for rows, val in ((k_rows, kv), (v_rows, vv)):
            r, s = int(np.asarray(rows)[b, 0]), int(of[b, 0])
            sc = got_sc[0, r, s]
            want_sc = np.abs(val[b, 0]).max() / 127.0
            np.testing.assert_allclose(sc, want_sc, rtol=1e-6)
            deq = got[r, s].astype(np.float32) * sc
            assert (np.abs(deq - val[b, 0].reshape(-1))
                    <= sc / 2 + 1e-7).all()
    # unwritten slots: zero bytes AND zero scales
    assert np.count_nonzero(got) > 0
    mask = np.ones(scales_shape, bool)
    for b in range(2):
        for rows in (k_rows, v_rows):
            mask[0, int(np.asarray(rows)[b, 0]), int(of[b, 0])] = False
    assert (got_sc[mask] == 0).all()

    # COW: page 2 <- page 1 moves int8 bytes and fp32 scales together
    main2 = fluid.Program()
    with fluid.program_guard(main2, fluid.Program()), \
            fluid.unique_name.guard():
        pool2 = main2.global_block().create_var(
            name="pool", shape=list(pool_shape), dtype="int8",
            persistable=True)
        scales2 = main2.global_block().create_var(
            name="scales", shape=list(scales_shape), dtype="float32",
            persistable=True)
        src = layers.data("src", [], "int32")
        dst = layers.data("dst", [], "int32")
        layers.paged_page_copy(pool2, src, dst, n_layer=L, scales=scales2)
    exe.run(main2, feed={"src": np.array([1, 0], np.int32),
                         "dst": np.array([2, 0], np.int32)},
            fetch_list=["pool"])
    after = np.asarray(scope.find_var("pool"))
    after_sc = np.asarray(scope.find_var("scales"))
    rows = np.arange(2 * L)
    np.testing.assert_array_equal(after[2 * 2 * L + rows],
                                  got[1 * 2 * L + rows])
    np.testing.assert_array_equal(after_sc[:, 2 * 2 * L + rows],
                                  got_sc[:, 1 * 2 * L + rows])


def test_ragged_pallas_interpret_matches_xla_int8():
    """The Pallas kernel's in-register dequant (block-scale rows DMA'd
    alongside each page) agrees with the XLA gather fallback on an int8
    pool, including the dead-lane zero contract (acceptance
    criterion)."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import ragged_decode_attention

    rng = np.random.RandomState(13)
    H, D, L, NPAGES, P, C, B = 2, 4, 3, 6, 3, 2, 3
    R = NPAGES * L * 2
    pool = jnp.asarray(rng.randint(-127, 128, (R, PS, H * D))
                       .astype(np.int8))
    scales = jnp.asarray(rng.uniform(1e-3, 0.1, (1, R, PS))
                         .astype(np.float32))
    q = jnp.asarray(rng.randn(B, C, H, D).astype(np.float32))
    tbl = jnp.asarray(rng.randint(0, NPAGES, (B, P)).astype(np.int32))
    lengths = jnp.asarray(np.array([7, 0, 11], np.int32))
    base = jnp.asarray(np.array([5, 0, 9], np.int32))
    for causal in (True, False):
        a = ragged_decode_attention(q, pool, tbl, lengths, base, layer=2,
                                    n_layer=L, causal=causal, impl="xla",
                                    scales=scales)
        b = ragged_decode_attention(q, pool, tbl, lengths, base, layer=2,
                                    n_layer=L, causal=causal,
                                    impl="pallas_interpret", scales=scales)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        assert (np.asarray(a)[1] == 0).all()       # dead lane contract


def test_ragged_pallas_interpret_matches_xla_bf16():
    """A bfloat16 pool decodes through the kernel's VMEM-level upcast
    branch (no scale sidecar): Pallas-interpret agrees with the XLA
    fallback, dead-lane zero contract included."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import ragged_decode_attention

    rng = np.random.RandomState(17)
    H, D, L, NPAGES, P, C, B = 2, 4, 3, 6, 3, 2, 3
    R = NPAGES * L * 2
    pool = jnp.asarray(rng.randn(R, PS, H * D).astype(np.float32),
                       jnp.bfloat16)
    q = jnp.asarray(rng.randn(B, C, H, D).astype(np.float32))
    tbl = jnp.asarray(rng.randint(0, NPAGES, (B, P)).astype(np.int32))
    lengths = jnp.asarray(np.array([7, 0, 11], np.int32))
    base = jnp.asarray(np.array([5, 0, 9], np.int32))
    for causal in (True, False):
        a = ragged_decode_attention(q, pool, tbl, lengths, base, layer=1,
                                    n_layer=L, causal=causal, impl="xla")
        b = ragged_decode_attention(q, pool, tbl, lengths, base, layer=1,
                                    n_layer=L, causal=causal,
                                    impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        assert (np.asarray(a)[1] == 0).all()       # dead lane contract


def test_bf16_kv_greedy_matches_float_pool():
    """kv_dtype="bfloat16" is a real decode mode, not just capacity
    math: greedy through a bf16 pool (cache writes cast into the pool,
    the attention walk upcasts in-register) tracks the float32 pool on
    seeded mixed-length prompts, with the hbm stats reporting the
    2-byte stream."""
    fp, bf = _kv_pool_pair("bfloat16", "tfb")
    _, (tok, lens) = _sources(4)
    g_fp = np.asarray(fp.greedy(tok, lens, max_new=OUT,
                                stop_at_end=False))
    g_bf = np.asarray(bf.greedy(tok, lens, max_new=OUT,
                                stop_at_end=False))
    assert (g_fp == g_bf).mean() >= 0.9, (g_fp, g_bf)
    st = bf.cache_stats()["hbm"]
    assert st["kv_dtype"] == "bfloat16"
    assert st["kv_bytes_per_token"] == bf.page_bytes // PS
    assert bf.page_bytes == fp.page_bytes // 2


def test_int8_kv_greedy_close_to_float_pool(int8_pair):
    """Greedy decode through the int8 pool (quantize-on-write, dequant
    in the attention walk) tracks the float32-pool decode on seeded
    mixed-length prompts, and the hbm stats expose the smaller stream:
    kv_bytes_per_token ranks int8 < bf16 < f32 with the fp32 scale
    sidecar honestly included."""
    from paddle_tpu.serving import kv_page_bytes

    fp, i8 = int8_pair
    _, (tok, lens) = _sources(0)
    g_fp = np.asarray(fp.greedy(tok, lens, max_new=OUT,
                                stop_at_end=False))
    g_i8 = np.asarray(i8.greedy(tok, lens, max_new=OUT,
                                stop_at_end=False))
    assert (g_fp == g_i8).mean() >= 0.9, (g_fp, g_i8)
    st = i8.cache_stats()["hbm"]
    assert st["kv_dtype"] == "int8"
    assert st["kv_bytes_per_token"] == i8.page_bytes // PS
    assert st["pool_bytes"] == i8.page_bytes * i8.num_pages
    bpt = {dt: kv_page_bytes(NL, NH, DK, PS, dt) // PS
           for dt in ("int8", "bfloat16", "float32")}
    assert bpt["int8"] < bpt["bfloat16"] < bpt["float32"]
    assert st["kv_bytes_per_token"] == bpt["int8"]
    # steady state: a second round through the int8 path compiles nothing
    misses0 = i8.cache_stats()["executable"]["misses"]
    _, (tok2, lens2) = _sources(8)
    i8.greedy(tok2, lens2, max_new=OUT, stop_at_end=False)
    assert i8.cache_stats()["executable"]["misses"] == misses0


def test_int8_beam_cow_keeps_scales_with_pages(int8_pair):
    """Beam search over the int8 pool: the copy-on-write reorder moves
    int8 pages + their block scales in one op, so shared-parent lanes
    decode sensible tokens (close to the float-pool beam) and nothing
    leaks."""
    fp, i8 = int8_pair
    W = 3
    _, (tok, lens) = _sources(2, n=2)
    f_ids, f_scores = fp.beam(tok, lens, beam_size=W, max_new=OUT)
    cow0 = i8.cache_stats()["pages"]["cow_copies"]
    q_ids, q_scores = i8.beam(tok, lens, beam_size=W, max_new=OUT)
    assert i8.cache_stats()["pages"]["cow_copies"] > cow0
    assert i8.cache_stats()["pages"]["in_use"] == 0
    assert (np.asarray(f_ids) == np.asarray(q_ids)).mean() >= 0.9
    np.testing.assert_allclose(np.asarray(q_scores),
                               np.asarray(f_scores), rtol=0.05, atol=0.2)
    i8.alloc.check_invariants()


def test_capacity_contest_int8_gt_bf16_gt_dense():
    """The PR 6 capacity contest extended per ISSUE 7: at the SAME
    simulated HBM budget, the int8 pool (1 byte/elem + fp32 block-scale
    sidecar) admits strictly more in-flight requests than the bf16 pool,
    which admits strictly more than dense worst-case reservation."""
    from paddle_tpu.serving import kv_page_bytes
    from paddle_tpu.serving.decoder import _Cfg, dense_kv_bytes_per_slot

    exe = fluid.Executor(fluid.CPUPlace())
    kw = dict(n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
              d_inner_hid=DI, max_length=64, src_len=SRC, executor=exe,
              max_out_len=OUT, page_size=PS, chunk_size=CHUNK)
    dense_slot = dense_kv_bytes_per_slot(
        _Cfg(V, V, NL, NH, DK, DK, DM, DI, 64), SRC, OUT)
    budget = 4 * dense_slot
    admitted = {}
    for dt in ("bfloat16", "int8"):
        gen = PagedTransformerGenerator(
            V, V, scope=fluid.Scope(), param_prefix=f"tfc_{dt}",
            num_pages=budget // kv_page_bytes(NL, NH, DK, PS, dt),
            kv_dtype=dt, **kw)
        assert gen.cache_stats()["hbm"]["pool_bytes"] <= budget
        rng = np.random.RandomState(9)
        gen.open_slots(64)
        n = 0
        while n < 64:
            prompt = rng.randint(2, V, int(rng.randint(2, SRC // 2 + 1)))
            if not gen.can_admit(prompt, max_new=PS):
                break
            gen.admit_slot(n, prompt, max_new=PS)
            n += 1
        admitted[dt] = n
    n_dense = budget // dense_slot
    assert admitted["int8"] > admitted["bfloat16"] > n_dense, \
        (admitted, n_dense)


# -- token-major pool, written in place (ISSUE 26) ----------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_write_then_read_matches_dense_reference(kv_dtype, impl,
                                                 fresh_programs):
    """paged_cache_write followed by ragged_decode_attention, through the
    ops, against a dense per-lane reference: two dispatches, so the
    second chunk of lane 0 starts mid-page and crosses a page boundary;
    lane 1 is dead throughout (trash page, length 0) and lane 2's chunk
    ends in a dead token, which goes to the trash page too.  The pool
    must hold every live token's [H*D] row at its (row, slot) and
    nothing else outside the trash page; the attention must equal a
    causal softmax over what the pool's dtype kept of K and V."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import paged_kv_rows

    main, startup, scope = fresh_programs
    H, D, L, LAYER, NPAGES, P, C, B = 2, 4, 2, 1, 8, 2, 3, 3
    R = NPAGES * L * 2
    block = main.global_block()
    pool = block.create_var(name="pool", shape=[R, PS, H * D],
                            dtype=kv_dtype, persistable=True)
    scope.set_var("pool", jnp.zeros((R, PS, H * D), kv_dtype))
    scales = None
    if kv_dtype == "int8":
        scales = block.create_var(name="scales", shape=[1, R, PS],
                                  dtype="float32", persistable=True)
        scope.set_var("scales", jnp.zeros((1, R, PS), jnp.float32))
    k = layers.data("k", [C, H, D], "float32")
    v = layers.data("v", [C, H, D], "float32")
    pages = layers.data("pages", [C], "int32")
    offs = layers.data("offs", [C], "int32")
    tbl = layers.data("tbl", [P], "int32")
    ln = layers.data("ln", [], "int32")
    qb = layers.data("qb", [], "int32")
    if scales is not None:
        pool, scales = layers.quantized_paged_cache_write(
            pool, scales, k, v, pages, offs, layer=LAYER, n_layer=L)
    else:
        pool = layers.paged_cache_write(pool, k, v, pages, offs,
                                        layer=LAYER, n_layer=L)
    out = layers.ragged_decode_attention(k, pool, tbl, ln, qb, layer=LAYER,
                                         n_layer=L, causal=True, impl=impl,
                                         scales=scales)
    exe = fluid.Executor(fluid.CPUPlace())
    table = np.array([[3, 5], [0, 0], [6, 0]], np.int32)
    # tokens live per lane in each of the two dispatches
    live = [[2, 0, 2], [3, 0, 3]]
    rng = np.random.RandomState(26)
    kept_k = [np.zeros((0, H, D), np.float32) for _ in range(B)]
    kept_v = [np.zeros((0, H, D), np.float32) for _ in range(B)]
    written = set()
    for step_live in live:
        kv_ = (rng.randn(B, C, H, D) * 2).astype(np.float32)
        vv_ = rng.randn(B, C, H, D).astype(np.float32)
        pg = np.zeros((B, C), np.int32)
        of = np.zeros((B, C), np.int32)
        base = np.array([len(x) for x in kept_k], np.int32)
        for b in range(B):
            for c in range(step_live[b]):
                pos = int(base[b]) + c
                pg[b, c], of[b, c] = table[b, pos // PS], pos % PS
        lengths = base + np.asarray(step_live, np.int32)
        got, = exe.run(main, feed={"k": kv_, "v": vv_, "pages": pg,
                                   "offs": of, "tbl": table, "ln": lengths,
                                   "qb": base}, fetch_list=[out])
        got = np.asarray(got)
        pool_np = np.asarray(scope.find_var("pool")).astype(np.float32)
        if kv_dtype == "int8":
            pool_np = pool_np * np.asarray(
                scope.find_var("scales"))[0][..., None]
        k_rows, v_rows = (np.asarray(x) for x in paged_kv_rows(pg, LAYER, L))
        tol = {"float32": 0.0, "bfloat16": 2.0 ** -8, "int8": 0.5 / 127}
        for b in range(B):
            for c in range(step_live[b]):
                for rows, val, kept in ((k_rows, kv_, kept_k),
                                        (v_rows, vv_, kept_v)):
                    row = pool_np[rows[b, c], of[b, c]].reshape(H, D)
                    assert np.abs(row - val[b, c]).max() <= \
                        tol[kv_dtype] * np.abs(val[b, c]).max() + 1e-7
                    kept[b] = np.concatenate([kept[b], row[None]])
                    written.add((int(rows[b, c]), int(of[b, c])))
        # outside the live tokens' slots only the trash page was written
        untouched = np.ones((R, PS), bool)
        untouched[:2 * L] = False
        for r, s in written:
            untouched[r, s] = False
        assert not pool_np[untouched].any()
        for b in range(B):
            for c in range(C):
                n = min(int(lengths[b]), int(base[b]) + c + 1)
                if not lengths[b]:
                    assert not got[b, c].any()     # dead lane contract
                    continue
                s = np.einsum("hd,khd->hk", kv_[b, c],
                              kept_k[b][:n]) * D ** -0.5
                p = np.exp(s - s.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                want = np.einsum("hk,khd->hd", p, kept_v[b][:n])
                np.testing.assert_allclose(got[b, c], want, rtol=2e-5,
                                           atol=2e-5)


def test_unified_step_holds_no_second_pool_sized_buffer():
    """The regression guard of ISSUE 26, on the program's side: in the
    optimized HLO of the donated unified step nothing but the pool
    parameter, its views and the in-place row scatters (two per write
    op: 3 write ops a layer) has the pool's element count.  No copy, no
    transpose, no other fusion.  (The head-major pool this replaced
    compiled to 13 transposes and 14 copies of the whole pool here, and
    to 14 copies of 2 GB on the chip.)"""
    gen = PagedTransformerGenerator(
        V, V, n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
        d_inner_hid=DI, max_length=64, src_len=SRC, max_out_len=OUT,
        page_size=PS, chunk_size=CHUNK, num_pages=67, param_prefix="hlo",
        executor=fluid.Executor(fluid.CPUPlace()))
    gen.init_params(seed=1)
    gen.open_slots(3)
    hlo = gen.compiled_step_hlo()
    assert "input_output_alias" in hlo.splitlines()[0]
    n_elems = int(np.prod(gen._pool_shape))     # 67 pages: no other match
    kinds = hlo_results_of_size(hlo, n_elems)
    n_writes = 2 * 3 * NL
    assert kinds.pop("scatter") == n_writes
    assert kinds.pop("fusion", n_writes) == n_writes   # one per scatter
    assert set(kinds) <= {"parameter", "bitcast"}, kinds


# what the head-major pool produced at e99c405 (PR 25) for these weights
# and prompts: the token-major pool must reproduce it token for token
_GOLDEN_SRC = [[8, 18, 3, 8, 21, 15, 2, 6], [15, 19, 21, 23, 22, 0, 0, 0],
               [23, 17, 12, 0, 0, 0, 0, 0], [20, 17, 23, 14, 17, 18, 0, 0]]
_GOLDEN_LENS = [8, 5, 3, 6]
_GOLDEN_GREEDY = [[11, 11, 11, 22, 22, 22, 1, 1], [11, 22, 1, 1, 1, 1, 1, 1],
                  [11, 11, 11, 11, 11, 11, 11, 11],
                  [11, 11, 11, 11, 11, 11, 11, 11]]
_GOLDEN_BEAM = [
    [[11, 22, 22, 1, 1, 1, 1, 1], [11, 11, 22, 1, 1, 1, 1, 1],
     [11, 11, 11, 22, 22, 22, 1, 1]],
    [[11, 22, 1, 1, 1, 1, 1, 1], [17, 11, 22, 22, 22, 22, 1, 1],
     [17, 11, 11, 22, 22, 22, 1, 1]],
    [[11, 11, 11, 22, 1, 1, 1, 1], [11, 11, 11, 11, 11, 11, 11, 11],
     [11, 11, 11, 11, 11, 11, 11, 22]],
    [[2, 11, 11, 11, 11, 11, 11, 11], [11, 11, 11, 11, 11, 11, 11, 11],
     [2, 11, 11, 11, 11, 11, 11, 22]]]
_GOLDEN_BEAM_SCORES = [
    [-7.833042144775391, -7.993684768676758, -13.988262176513672],
    [-6.084239482879639, -13.333710670471191, -13.369152069091797],
    [-9.643131256103516, -14.632216453552246, -14.970511436462402],
    [-11.750408172607422, -11.869285583496094, -12.717007637023926]]


@pytest.fixture(scope="module")
def golden_pair():
    from paddle_tpu.serving import SpeculativeGenerator

    scope = fluid.Scope()
    kw = dict(n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
              d_inner_hid=DI, max_length=64, src_len=SRC, max_out_len=OUT,
              page_size=PS, chunk_size=CHUNK, num_pages=64, scope=scope,
              executor=fluid.Executor(fluid.CPUPlace()))
    target = PagedTransformerGenerator(V, V, param_prefix="gold", **kw)
    draft = PagedTransformerGenerator(V, V, param_prefix="golddraft", **kw)
    target.init_params(seed=11)
    draft.init_params(seed=12)
    return target, SpeculativeGenerator(target, draft, k=3,
                                        draft_name="golddraft")


@pytest.mark.parametrize("mode", ["greedy", "beam", "speculative"])
def test_decoding_reproduces_head_major_pool_outputs(mode, golden_pair):
    """Greedy, beam (reorders reassign page tables and copy shared pages
    on write) and speculative decoding (K-token verify writes, rollback
    by truncation; the reseeded draft is rejected 15 times in 16) emit
    exactly what they emitted over the head-major pool."""
    target, spec = golden_pair
    src = np.asarray(_GOLDEN_SRC, np.int64)
    lens = np.asarray(_GOLDEN_LENS, np.int32)
    if mode == "beam":
        ids, scores = target.beam(src, lens, beam_size=3, max_new=OUT)
        np.testing.assert_array_equal(np.asarray(ids), _GOLDEN_BEAM)
        np.testing.assert_allclose(np.asarray(scores), _GOLDEN_BEAM_SCORES,
                                   rtol=1e-5)
        return
    gen = target if mode == "greedy" else spec
    got = gen.greedy(src, lens, max_new=OUT, stop_at_end=False)
    np.testing.assert_array_equal(np.asarray(got), _GOLDEN_GREEDY)
    if mode == "speculative":
        assert spec.cache_stats()["speculative"]["accept_rate"] < 0.5
