"""Speculative + constrained decoding tests (ISSUE 15).

The load-bearing property is PARITY: whatever the draft model proposes
and whatever fraction of it the target accepts, the emitted tokens are
exactly what plain greedy decoding of the target would have produced —
speculation only changes how many target dispatches the tokens cost.
Everything else hangs off that: accept/reject rollback is host-side
page-table truncation (invariant-checked under prefix sharing and
copy-on-write), constraints mask both models' logits in-graph so
outputs always satisfy the grammar, mixed speculative/plain traffic
shares one verify executable with zero recompiles, and the gateway
carries draft/constraint options per request through the journal."""

import os

import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                PagedTransformerGenerator,
                                PoolCapacityError, SpeculativeGenerator,
                                copy_weights)
from paddle_tpu.serving.constraints import (DFAConstraint, MASKED,
                                            TokenSetConstraint,
                                            compile_constraint)
from paddle_tpu.serving.gateway import Gateway, ModelRegistry
from paddle_tpu.serving.paged_decoder import tower_widths

V, NL, NH, DK, DM, DI = 24, 2, 2, 4, 16, 32
SRC, OUT, PS, CHUNK = 8, 8, 4, 4
END = 1

KW = dict(n_layer=NL, n_head=NH, d_key=DK, d_value=DK, d_model=DM,
          d_inner_hid=DI, max_length=64, src_len=SRC, max_out_len=OUT,
          page_size=PS, chunk_size=CHUNK, num_pages=64)


@pytest.fixture(scope="module")
def spec_pair():
    """(speculative generator with draft == target, the bare target,
    a mismatched-draft speculative generator) over one scope.  The
    identical-weight draft is the accept-rate-1.0 configuration; the
    reseeded draft disagrees almost always — parity must hold for
    both."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    kw = dict(KW, scope=scope, executor=exe)
    target = PagedTransformerGenerator(V, V, param_prefix="tgt", **kw)
    same = PagedTransformerGenerator(V, V, param_prefix="dsame", **kw)
    other = PagedTransformerGenerator(V, V, param_prefix="dother", **kw)
    target.init_params(seed=7)
    copy_weights(scope, scope, prefix="tgt", dst_prefix="dsame")
    with fluid.scope_guard(scope):
        other._unified[1].random_seed = 99
        exe.run(other._unified[1])
    spec = SpeculativeGenerator(target, same, k=3, draft_name="dsame")
    spec_mm = SpeculativeGenerator(target, other, k=3,
                                   draft_name="dother")
    return spec, target, spec_mm


def _sources(seed=0, n=4):
    rng = np.random.RandomState(seed)
    seqs = [rng.randint(2, V, rng.randint(3, SRC + 1)) for _ in range(n)]
    src = np.zeros((n, SRC), np.int64)
    lens = np.zeros(n, np.int32)
    for i, s in enumerate(seqs):
        src[i, :len(s)] = s
        lens[i] = len(s)
    return seqs, src, lens


def _trunc_at_end(row):
    row = [int(t) for t in row]
    return row[:row.index(END) + 1] if END in row else row


# -- parity -------------------------------------------------------------------

def test_draft_equals_target_parity_accept_one(spec_pair):
    """draft == target: every draft token verifies, accept rate is
    exactly 1.0, output is token-for-token the plain paged greedy, and
    the whole batch costs ~max_new/(k+1) verify dispatches."""
    spec, target, _ = spec_pair
    _, src, lens = _sources(seed=0)
    ref = target.greedy(src, lens, max_new=OUT, stop_at_end=False)
    v0 = spec.cache_stats()["speculative"]["verify_steps"]
    out = spec.greedy(src, lens, max_new=OUT, stop_at_end=False)
    np.testing.assert_array_equal(ref, out)
    st = spec.cache_stats()["speculative"]
    assert st["accept_rate"] == 1.0
    # 8 tokens at k=3 -> ceil(8/4)+1(prefill rides) verify dispatches,
    # far under the 8 a plain path pays; bound it loosely
    assert st["verify_steps"] - v0 <= OUT // 2 + 2
    # dense stop-at-end semantics survive the multi-token rounds
    ref_e = target.greedy(src, lens, max_new=OUT, stop_at_end=True)
    out_e = spec.greedy(src, lens, max_new=OUT, stop_at_end=True)
    np.testing.assert_array_equal(ref_e, out_e)


def test_mismatched_draft_still_exact(spec_pair):
    """A draft that disagrees with the target must cost speed, never
    correctness: rejected tokens roll back by position truncation and
    the emitted sequence is still exactly the target's greedy."""
    spec, target, spec_mm = spec_pair
    _, src, lens = _sources(seed=1)
    ref = target.greedy(src, lens, max_new=OUT, stop_at_end=False)
    out = spec_mm.greedy(src, lens, max_new=OUT, stop_at_end=False)
    np.testing.assert_array_equal(ref, out)
    st = spec_mm.cache_stats()["speculative"]
    assert st["drafted"] > 0 and st["accept_rate"] < 1.0
    spec_mm.check_invariants()


def test_speculation_disabled_parity(spec_pair):
    """decode={"draft": False} lanes ride the verify executable as
    plain 1-token decode — same tokens, no draft dispatches for them."""
    spec, target, _ = spec_pair
    _, src, lens = _sources(seed=2)
    ref = target.greedy(src, lens, max_new=OUT, stop_at_end=False)
    d0 = spec.cache_stats()["speculative"]["draft_steps"]
    out = spec.greedy(src, lens, max_new=OUT, stop_at_end=False,
                      speculative=False)
    np.testing.assert_array_equal(ref, out)
    # the draft ran only its (cheap) prefill-less idle dispatches: no
    # lane ever drafted, so no drafted tokens were recorded
    assert spec.cache_stats()["speculative"]["draft_steps"] == d0


def test_zero_recompiles_across_speculative_traffic(spec_pair):
    """After one warm batch, further mixed traffic adds no executable
    misses on EITHER program — the zero-recompile contract covers the
    draft and verify executables."""
    spec, _, _ = spec_pair
    _, src, lens = _sources(seed=3)
    spec.greedy(src, lens, max_new=OUT, stop_at_end=False)
    c0 = spec.cache_stats()
    _, src2, lens2 = _sources(seed=4)
    spec.greedy(src2, lens2, max_new=OUT, stop_at_end=False)
    spec.greedy(src2, lens2, max_new=OUT, stop_at_end=False,
                speculative=False)
    c1 = spec.cache_stats()
    assert c1["executable"]["misses"] == c0["executable"]["misses"]
    assert c1["draft_executable"]["misses"] == \
        c0["draft_executable"]["misses"]


SPEC_LANES = 16
SPEC_WIDTHS = tower_widths(SPEC_LANES)        # (2, 16)


@pytest.fixture(scope="module")
def wide_spec():
    """A 16-lane speculative pair (draft == target) whose ``aot_warm``
    ran: the prefill tower of BOTH programs is 2 or 16 rows wide."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    kw = dict(KW, scope=scope, executor=exe, num_pages=16 * SPEC_LANES,
              prefix_sharing=False)
    target = PagedTransformerGenerator(V, V, param_prefix="wt", **kw)
    draft = PagedTransformerGenerator(V, V, param_prefix="wd", **kw)
    target.init_params(seed=5)
    copy_weights(scope, scope, prefix="wt", dst_prefix="wd")
    spec = SpeculativeGenerator(target, draft, k=3, draft_name="wd")
    spec.aot_warm(SPEC_LANES)
    return spec, target


@pytest.mark.parametrize("width", SPEC_WIDTHS)
def test_aot_warm_resolves_draft_and_verify_at_every_tower_width(
        width, wide_spec):
    """The pair's ``aot_warm`` resolved the draft AND the verify
    program at every width of the prefill tower: a batch whose
    prefilling lanes need ``width`` rows adds no executable-cache miss
    on either executor, and decodes what the plain target decodes."""
    spec, target = wide_spec
    assert len(spec.bucket_set(SPEC_LANES)) == 2 * len(SPEC_WIDTHS) + 1
    n = width if width < SPEC_LANES else SPEC_WIDTHS[-2] + 1
    _, src, lens = _sources(seed=30 + width, n=SPEC_LANES)
    lens[n:] = 0                                # the other lanes stay idle
    c0 = spec.cache_stats()
    by_width0 = dict(target.counters()["steps_by_width"])
    spec.open_slots(SPEC_LANES)
    for slot in range(n):
        spec.admit_slot(slot, src[slot, :lens[slot]], max_new=OUT)
    out = [[] for _ in range(n)]
    while any(len(row) < OUT for row in out):
        for slot, toks in spec.lane_step().items():
            out[slot].extend(toks)
    for slot in range(n):
        spec.clear_slot(slot)
    c1 = spec.cache_stats()
    assert c1["executable"]["misses"] == c0["executable"]["misses"]
    assert c1["draft_executable"]["misses"] == \
        c0["draft_executable"]["misses"]
    by_width = target.counters()["steps_by_width"]
    assert by_width.get(width, 0) > by_width0.get(width, 0)
    target.open_slots(SPEC_LANES)
    ref = target.greedy(src[:n], lens[:n], max_new=OUT, stop_at_end=False)
    np.testing.assert_array_equal(
        np.asarray([row[:OUT] for row in out]), ref)


# -- rollback / COW / invariants ---------------------------------------------

def test_rollback_truncation_under_prefix_sharing(spec_pair):
    """Speculative rounds over lanes whose prompts SHARE prefix-cached
    chunks: verification writes only lane-owned self pages (shared
    enc/cross pages are read-only on the decode path), rollback is pure
    position truncation, and the allocator invariants hold after every
    round."""
    spec, target, spec_mm = spec_pair
    rng = np.random.RandomState(5)
    base = rng.randint(2, V, SRC)        # one full-page shared prefix
    n = 3
    src = np.tile(base, (n, 1)).astype(np.int64)
    src[1:, PS:] = rng.randint(2, V, (n - 1, SRC - PS))
    lens = np.full(n, SRC, np.int32)
    ref = target.greedy(src, lens, max_new=OUT, stop_at_end=False)

    spec_mm.open_slots(n)
    hits0 = spec_mm.target.alloc.stats()["prefix_hits"]
    spec_mm.admit_slot(0, src[0], max_new=OUT)
    out = [[] for _ in range(n)]
    # let lane 0's prefill finish (its full chunks enter the prefix
    # cache), THEN admit the sharers: their admissions HIT the cached
    # chunk, so the shared enc/cross pages carry refcount > 1 while
    # speculative rounds verify and roll back over them
    while spec_mm.target._lanes[0].phase == "prefill":
        spec_mm.lane_step()
    for i in range(1, n):
        spec_mm.admit_slot(i, src[i], max_new=OUT)
    assert spec_mm.target.alloc.stats()["prefix_hits"] > hits0
    while any(len(o) < OUT for o in out):
        for slot, toks in spec_mm.lane_step().items():
            out[slot].extend(toks)
        spec_mm.check_invariants()       # after EVERY round
    for i in range(n):
        spec_mm.clear_slot(i)
    spec_mm.check_invariants()
    np.testing.assert_array_equal(
        ref, np.asarray([o[:OUT] for o in out], np.int64))


def test_cow_shared_self_page_not_mutated(spec_pair):
    """A self page some other holder still references is COW-copied
    BEFORE the verify dispatch writes: the shared bytes stay identical,
    the lane continues on its private copy, refcounts stay exact."""
    spec, target, _ = spec_pair
    seqs, _, _ = _sources(seed=6, n=1)
    spec.open_slots(1)
    spec.admit_slot(0, seqs[0], max_new=OUT)
    while spec.target._lanes[0].phase == "prefill" or \
            spec.draft._lanes[0].phase == "prefill":
        spec.lane_step()
    tl = spec.target._lanes[0]
    shared = tl.self_table[0]
    spec.target.alloc.ref(shared)        # an external holder appears
    cow0 = spec.cache_stats()["speculative"]["cow_copies"]
    pool_before = np.asarray(
        target.scope.find_var("tgt@kv_pool")).copy()
    spec.lane_step()
    assert tl.self_table[0] != shared
    assert spec.cache_stats()["speculative"]["cow_copies"] == cow0 + 1
    spec.check_invariants()
    rows = np.arange(2 * NL) + shared * 2 * NL
    pool_after = np.asarray(target.scope.find_var("tgt@kv_pool"))
    np.testing.assert_array_equal(pool_before[rows], pool_after[rows])
    spec.target.alloc.unref(shared)
    spec.clear_slot(0)
    spec.check_invariants()


def test_cow_pool_exhaustion_aborts_before_surgery(spec_pair):
    """A pool-capacity failure allocating COW copies must abort the
    round BEFORE any page-table surgery — a partially-committed COW
    would leave a lane pointing at a never-copied page and silently
    decode from garbage K/V.  The table is untouched, invariants hold,
    and the shared page's bytes survive."""
    spec, target, _ = spec_pair
    seqs, _, _ = _sources(seed=13, n=1)
    spec.open_slots(1)
    spec.admit_slot(0, seqs[0], max_new=OUT)
    while spec.target._lanes[0].phase == "prefill" or \
            spec.draft._lanes[0].phase == "prefill":
        spec.lane_step()
    alloc = spec.target.alloc
    tl = spec.target._lanes[0]
    shared = tl.self_table[0]
    alloc.ref(shared)                    # external holder forces COW
    hog = []                             # drain free AND evictable
    try:
        while True:
            try:
                hog.extend(alloc.alloc(1))
            except PoolCapacityError:
                break
        table_before = list(tl.self_table)
        pool_before = np.asarray(
            target.scope.find_var("tgt@kv_pool")).copy()
        with pytest.raises(PoolCapacityError):
            spec.lane_step()
        assert list(tl.self_table) == table_before   # no surgery
        spec.check_invariants()
        rows = np.arange(2 * NL) + shared * 2 * NL
        np.testing.assert_array_equal(
            pool_before[rows],
            np.asarray(target.scope.find_var("tgt@kv_pool"))[rows])
    finally:
        for p in hog:
            alloc.unref(p)
        alloc.unref(shared)
        spec.clear_slot(0)
    spec.check_invariants()


def test_rollback_to_continuation_parity(spec_pair):
    """Explicit rollback_to: truncate to an earlier committed point and
    keep decoding — the continuation re-derives exactly the tokens the
    first pass produced (greedy is a function of the committed
    prefix)."""
    spec, _, _ = spec_pair
    seqs, _, _ = _sources(seed=7, n=1)
    spec.open_slots(1)
    spec.admit_slot(0, seqs[0], max_new=OUT)
    got = []
    while len(got) < 5:
        for _, toks in spec.lane_step().items():
            got.extend(toks)
    spec.rollback_to(0, 2, got[1])
    tl = spec.target._lanes[0]
    assert (tl.pos, tl.cur) == (2, got[1])
    cont = []
    while len(cont) < 3:
        for _, toks in spec.lane_step().items():
            cont.extend(toks)
    assert cont[:3] == got[2:5]
    spec.clear_slot(0)
    spec.check_invariants()


def test_admit_draft_pool_refusal_releases_target_pages():
    """All-or-nothing admission: a draft pool too small for the request
    refuses the admit AND releases the pages the target half already
    took."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    kw = dict(KW, scope=scope, executor=exe)
    target = PagedTransformerGenerator(V, V, param_prefix="tp", **kw)
    tiny = PagedTransformerGenerator(
        V, V, param_prefix="dp", **dict(kw, num_pages=4))
    target.init_params(seed=0)
    copy_weights(scope, scope, prefix="tp", dst_prefix="dp")
    spec = SpeculativeGenerator(target, tiny, k=2)
    spec.open_slots(1)
    free_before = target.alloc.available()
    with pytest.raises(PoolCapacityError):
        spec.admit_slot(0, np.arange(2, 2 + SRC), max_new=OUT)
    assert target.alloc.available() == free_before
    spec.check_invariants()


# -- constraints --------------------------------------------------------------

def test_constrained_outputs_satisfy_token_set(spec_pair):
    """Every emitted token of a token_set-constrained request is in the
    allowed set (+ end), speculative or not, and the two modes agree
    token for token."""
    spec, _, _ = spec_pair
    _, src, lens = _sources(seed=8)
    allowed = {4, 5, 6}
    c = {"type": "token_set", "allowed": sorted(allowed)}
    out = spec.greedy(src, lens, max_new=OUT, stop_at_end=False,
                      constraint=c)
    assert all(int(t) in allowed | {END} for row in out for t in row)
    out_off = spec.greedy(src, lens, max_new=OUT, stop_at_end=False,
                          constraint=c, speculative=False)
    np.testing.assert_array_equal(out, out_off)


def test_constrained_outputs_satisfy_dfa(spec_pair):
    """DFA-constrained generation follows the automaton exactly: tokens
    alternate between the two edge sets, end only in accepting states,
    and nothing but end after the end (terminal parking)."""
    spec, _, spec_mm = spec_pair
    _, src, lens = _sources(seed=9)
    edges = [["a", t, "b"] for t in (2, 3)] + \
            [["b", t, "a"] for t in (8, 9)]
    dfa = {"type": "dfa", "start": "a", "edges": edges, "accept": ["a"]}
    for gen in (spec, spec_mm):      # high AND low accept rates
        out = gen.greedy(src, lens, max_new=OUT, stop_at_end=False,
                         constraint=dfa)
        for row in out:
            state = "a"
            for t in row:
                t = int(t)
                if state == "TERM":
                    assert t == END
                    continue
                if t == END:
                    assert state == "a"
                    state = "TERM"
                    continue
                assert t in ({"a": {2, 3}, "b": {8, 9}}[state])
                state = "b" if state == "a" else "a"


def test_constraint_objects_and_errors():
    """Wire-format validation + precompiled mask rows."""
    c = compile_constraint({"type": "token_set", "allowed": [3, 4]},
                           V, END)
    assert isinstance(c, TokenSetConstraint)
    row = c.mask(c.start_state())
    assert row[3] == 0.0 and row[4] == 0.0 and row[END] == 0.0
    assert row[5] == MASKED
    d = compile_constraint(
        {"type": "dfa", "start": 0, "edges": [[0, 2, 1], [1, 3, 0]],
         "accept": [0]}, V, END)
    assert isinstance(d, DFAConstraint)
    s = d.start_state()
    assert d.allows(s, 2) and not d.allows(s, 3)
    assert d.allows(s, END)              # accepting start
    s2 = d.advance(s, 2)
    assert d.allows(s2, 3) and not d.allows(s2, END)
    with pytest.raises(ValueError):
        compile_constraint({"type": "token_set"}, V, END)
    with pytest.raises(ValueError):
        compile_constraint({"type": "nope"}, V, END)
    with pytest.raises(ValueError):     # dead-end state
        compile_constraint(
            {"type": "dfa", "start": 0, "edges": [[0, 2, 1]],
             "accept": []}, V, END)
    with pytest.raises(ValueError):     # empty allowed set
        TokenSetConstraint([], V, end_id=None)
    with pytest.raises(ValueError):     # oversized edge token id
        compile_constraint(
            {"type": "dfa", "start": 0, "edges": [[0, V + 10, 0]],
             "accept": [0]}, V, END)
    with pytest.raises(ValueError):     # negative id would wrap-index
        compile_constraint(
            {"type": "dfa", "start": 0, "edges": [[0, -1, 0]],
             "accept": [0]}, V, END)


# -- scheduler integration ----------------------------------------------------

def test_scheduler_mixed_speculative_plain_integrity(spec_pair):
    """Seeded sweep: a dozen requests with interleaved speculative /
    plain / constrained decode options through 3 lanes — zero lost or
    duplicated requests, every unconstrained request token-for-token
    equal to the plain-greedy reference, allocator invariants clean."""
    spec, target, _ = spec_pair
    seqs, src, lens = _sources(seed=10, n=12)
    ref_rows = target.greedy(src, lens, max_new=OUT, stop_at_end=False)
    refs = [_trunc_at_end(r) for r in ref_rows]
    sched = ContinuousBatchingScheduler(spec, n_slots=3,
                                        max_new_tokens=OUT)
    allowed = {4, 5, 6}
    reqs = []
    for i, s in enumerate(seqs):
        decode = {"draft": i % 2 == 0}
        if i % 3 == 2:
            decode["constraint"] = {"type": "token_set",
                                    "allowed": sorted(allowed)}
        reqs.append(sched.submit(s, max_new_tokens=OUT, decode=decode))
    sched.run_until_idle()
    seen = set()
    for i, r in enumerate(reqs):
        assert r.done and r.error is None, (i, r.error)
        assert r.rid not in seen
        seen.add(r.rid)
        if i % 3 == 2:
            assert all(t in allowed | {END} for t in r.tokens), \
                (i, r.tokens)
        else:
            assert r.tokens == refs[i], (i, r.tokens, refs[i])
    st = sched.stats()
    assert st["finished"] == len(reqs) and st["failed"] == 0
    spec.check_invariants()


def test_scheduler_rejects_decode_options_on_plain_group(spec_pair):
    _, target, _ = spec_pair
    sched = ContinuousBatchingScheduler(target, n_slots=2,
                                        max_new_tokens=OUT)
    with pytest.raises(ValueError):
        sched.submit(np.arange(2, 6), max_new_tokens=4,
                     decode={"draft": True})


def test_decode_request_rerouted_to_plain_group_is_rejected(spec_pair):
    """A constrained request whose alias re-resolves to a PLAIN group
    between submit and admission (hot swap / canary fallback) must be
    REJECTED, never silently served without its grammar."""
    spec, target, _ = spec_pair
    routes = {"m": "spec"}
    sched = ContinuousBatchingScheduler(
        max_new_tokens=OUT, resolve=lambda alias: routes.get(alias,
                                                             alias))
    sched.add_model("spec", spec, 2)
    sched.add_model("plain", target, 2)
    req = sched.submit(np.arange(2, 6), max_new_tokens=4, model="m",
                       decode={"constraint": {"type": "token_set",
                                              "allowed": [4, 5]}})
    routes["m"] = "plain"       # the swap lands before admission
    sched.run_until_idle()
    assert req.done and isinstance(req.error, ValueError), req.error
    assert req.tokens == []     # nothing was served off-grammar
    # and a plain request keeps flowing through the same alias
    ok = sched.submit(np.arange(2, 6), max_new_tokens=4, model="m")
    sched.run_until_idle()
    assert ok.done and ok.error is None
    # an explicit speculation OPT-OUT ({"draft": False}, no grammar)
    # re-routed the same way is ADMITTED plain — plain decode is
    # exactly what it asked for, so rejection would be spurious
    routes["m"] = "spec"
    optout = sched.submit(np.arange(2, 6), max_new_tokens=4, model="m",
                          decode={"draft": False})
    routes["m"] = "plain"
    sched.run_until_idle()
    assert optout.done and optout.error is None
    assert optout.tokens == ok.tokens
    # the submit-time gate agrees: an opt-out submitted DIRECTLY to a
    # plain group (what journal replay does after a restart onto a
    # draftless version) is accepted, not 400d
    direct = sched.submit(np.arange(2, 6), max_new_tokens=4,
                          model="plain", decode={"draft": False})
    sched.run_until_idle()
    assert direct.done and direct.error is None
    assert direct.tokens == ok.tokens
    with pytest.raises(ValueError):     # a grammar still refuses
        sched.submit(np.arange(2, 6), max_new_tokens=4, model="plain",
                     decode={"constraint": {"type": "token_set",
                                            "allowed": [4]}})


def test_beam_speculative_mutual_exclusion(spec_pair):
    spec, _, _ = spec_pair
    with pytest.raises(NotImplementedError):
        spec.beam(np.zeros((1, SRC), np.int64),
                  np.full(1, SRC, np.int32), beam_size=2)
    with pytest.raises(ValueError):
        spec.open_slots(1)
        spec.admit_slot(0, np.arange(2, 6), max_new=4,
                        decode={"beam": 2})


# -- HBM budgeting ------------------------------------------------------------

def test_static_hbm_estimate_prices_pair(spec_pair):
    """The joint plan covers both pools and the verify-shape
    activations; components name target.* and draft.* so an
    HBMBudgetError is attributable."""
    spec, target, _ = spec_pair
    plan = spec.static_hbm_estimate(assume_lanes=4)
    t_alone = target.static_hbm_estimate(assume_lanes=4)
    assert plan.peak_bytes > t_alone.peak_bytes
    comps = plan.components
    assert any(k.startswith("target.") for k in comps)
    assert any(k.startswith("draft.") for k in comps)
    # pools are persistable state in both halves
    assert comps.get("target.kv_pool", 0) > 0
    assert comps.get("draft.kv_pool", 0) > 0


def test_scheduler_budget_refuses_oversized_pair(spec_pair):
    spec, _, _ = spec_pair
    need = spec.static_hbm_estimate(assume_lanes=2).peak_bytes
    from paddle_tpu.serving.scheduler import HBMBudgetError
    sched = ContinuousBatchingScheduler(max_new_tokens=OUT,
                                        hbm_budget_bytes=need // 2)
    with pytest.raises(HBMBudgetError):
        sched.add_model("s", spec, 2)
    sched2 = ContinuousBatchingScheduler(max_new_tokens=OUT,
                                         hbm_budget_bytes=need * 2)
    sched2.add_model("s", spec, 2)
    assert sched2.stats()["models"]["s"]["static_hbm_bytes"] == need


# -- gateway ------------------------------------------------------------------

def test_gateway_speculative_end_to_end(tmp_path, spec_pair):
    """The full request path: draft/constraint/speculate fields through
    submit, stream parity, validation failures, and a journal that
    replays decode options across a 'restart'."""
    spec, target, _ = spec_pair
    seqs, src, lens = _sources(seed=11, n=4)
    ref_rows = target.greedy(src, lens, max_new=OUT, stop_at_end=False)
    refs = [_trunc_at_end(r) for r in ref_rows]
    jpath = os.path.join(str(tmp_path), "req.jsonl")
    gw = Gateway(n_slots=3, max_new_tokens=OUT, journal_path=jpath)
    gw.load_model("m", "1", instance=spec)
    gw.serve()
    try:
        out = gw.generate("m", [int(t) for t in seqs[0]], max_new=OUT,
                          timeout=60)
        assert out["tokens"] == refs[0]
        out_plain = gw.generate("m", [int(t) for t in seqs[1]],
                                max_new=OUT, speculate=False, timeout=60)
        assert out_plain["tokens"] == refs[1]
        allowed = {4, 5, 6}
        out_c = gw.generate(
            "m", [int(t) for t in seqs[2]], max_new=OUT, timeout=60,
            constraint={"type": "token_set", "allowed": sorted(allowed)})
        assert all(t in allowed | {END} for t in out_c["tokens"])
        with gw.submit_stream("m", [int(t) for t in seqs[3]],
                              max_new=OUT) as stream:
            streamed = list(stream)
        assert streamed == refs[3]
        with pytest.raises(ValueError):
            gw.generate("m", [2, 3], draft_model="not-the-draft",
                        timeout=60)
        with pytest.raises(ValueError):     # malformed grammar: 400 path
            gw.generate("m", [2, 3], constraint={"type": "nope"},
                        timeout=60)
    finally:
        gw.shutdown(drain=True)
    assert gw.journal.pending() == []

    # plain groups refuse decode options at submit...
    gw2 = Gateway(n_slots=2, max_new_tokens=OUT)
    gw2.load_model("p", "1", instance=target)
    with pytest.raises(ValueError):
        gw2.submit("p", [2, 3], constraint={"type": "token_set",
                                            "allowed": [4]})
    with pytest.raises(ValueError):
        gw2.submit("p", [2, 3], speculate=True)
    # ...but an explicit speculate=False OPT-OUT is served plain — it
    # asks for nothing a plain group cannot do
    req = gw2.submit("p", [2, 3], speculate=False, max_new=4)
    gw2.run_until_idle()
    assert req.done and req.error is None and len(req.tokens) > 0


def test_journal_replays_decode_options(tmp_path, spec_pair):
    """A journaled constrained+speculative request survives a restart
    with its decode options intact: the recovered request decodes under
    the SAME grammar."""
    spec, _, _ = spec_pair
    seqs, _, _ = _sources(seed=12, n=1)
    jpath = os.path.join(str(tmp_path), "replay.jsonl")
    allowed = {4, 5, 6}
    c = {"type": "token_set", "allowed": sorted(allowed)}
    gw = Gateway(n_slots=2, max_new_tokens=OUT, journal_path=jpath)
    gw.load_model("m", "1", instance=spec)
    # journaled but never served: the "process died before the loop ran"
    gw.submit("m", [int(t) for t in seqs[0]], max_new=OUT, constraint=c)
    assert len(gw.journal.pending()) == 1
    assert gw.journal.pending()[0]["decode"]["constraint"] == c

    gw2 = Gateway(n_slots=2, max_new_tokens=OUT, journal_path=jpath)
    gw2.load_model("m", "1", instance=spec)
    replayed = gw2.recover()
    assert len(replayed) == 1 and replayed[0].decode["constraint"] == c
    gw2.run_until_idle()
    assert replayed[0].done and replayed[0].error is None
    assert all(t in allowed | {END} for t in replayed[0].tokens)
    assert gw2.journal.pending() == []


# -- registry artifacts -------------------------------------------------------

def test_registry_load_speculative_budget_and_warm(tmp_path):
    """load_speculative: joint costing BEFORE construction (a too-small
    budget refuses with draft.* components named), and a loaded pair
    decodes at its warmed lane count without another compile."""
    root = str(tmp_path)
    kw = dict(n_layer=1, n_head=2, d_key=4, d_value=4, d_model=16,
              d_inner_hid=32, max_length=64, src_len=SRC,
              max_out_len=OUT, page_size=PS, chunk_size=CHUNK,
              num_pages=32, place=fluid.CPUPlace())
    tgt = PagedTransformerGenerator(V, V, param_prefix="tg", **kw)
    tgt.init_params(seed=1)
    dr = PagedTransformerGenerator(V, V, param_prefix="dg", **kw)
    copy_weights(tgt.scope, dr.scope, prefix="tg", dst_prefix="dg")
    ModelRegistry.save_generator_artifact(tgt, root, "big", "1")
    ModelRegistry.save_generator_artifact(dr, root, "small", "1")

    from paddle_tpu.serving.scheduler import HBMBudgetError
    reg_small = ModelRegistry(root=root, hbm_budget_bytes=1024,
                              place=fluid.CPUPlace())
    with pytest.raises(HBMBudgetError) as ei:
        reg_small.load_speculative("big", "1", "small", "1", k=2)
    assert "draft." in str(ei.value)

    reg = ModelRegistry(root=root, place=fluid.CPUPlace())
    key = reg.load_speculative("big", "1", "small", "1", k=2)
    inst = reg.instance(key)
    assert reg.entries()[0]["kind"] == "speculative"
    inst.aot_warm(2)
    # draft and verify at both widths of the prefill tower (1 and 2
    # rows at 2 lanes), and the page copy
    assert len(inst.bucket_set(2)) == 5
    warm = [exe_half.cache_stats()["executable"]["misses"]
            for exe_half in (inst.target.exe, inst.draft.exe)]
    # decode at the warmed lane count: batch == n_slots == 2, so the
    # dispatch signatures are the warmed ones
    out = inst.greedy(np.asarray([[3, 4, 5, 6], [6, 5, 4, 3]], np.int64),
                      np.asarray([4, 4], np.int32), max_new=4,
                      stop_at_end=False)
    assert out.shape == (2, 4)
    assert warm == [exe_half.cache_stats()["executable"]["misses"]
                    for exe_half in (inst.target.exe, inst.draft.exe)]

    # an in-flight load of the same key makes a concurrent duplicate
    # fail FAST (reservation) instead of double-building the pair on
    # device and silently overwriting the first entry
    reg2 = ModelRegistry(root=root, place=fluid.CPUPlace())
    reg2._loading.add("big@1")
    with pytest.raises(ValueError, match="already loaded"):
        reg2.load("big", "1")
    with pytest.raises(ValueError, match="already loaded"):
        reg2.load_speculative("big", "1", "small", "1", k=2)
    reg2._loading.clear()
    # a FAILED load releases its reservation (the finally path)
    with pytest.raises(FileNotFoundError):
        reg2.load("big", "9")
    assert "big@9" not in reg2._loading
    reg2.load("big", "1")           # reservation gone: loads fine


def test_constraint_cache_byte_budget(spec_pair):
    """The compiled-constraint memo evicts by resident mask BYTES, not
    just entry count — a few huge grammars must not pin unbounded host
    memory — while the just-inserted entry always stays resident."""
    spec, _, _ = spec_pair
    spec._constraint_cache.clear()
    spec._constraint_bytes = 0
    row = V * 4                       # one float32 [vocab] mask row
    spec._CONSTRAINT_CACHE_MAX_BYTES = 2 * row   # instance shadow
    try:
        spec.compile_constraint({"type": "token_set", "allowed": [3]})
        spec.compile_constraint({"type": "token_set", "allowed": [4]})
        assert len(spec._constraint_cache) == 2
        spec.compile_constraint({"type": "token_set", "allowed": [5]})
        assert len(spec._constraint_cache) == 2      # oldest evicted
        assert spec._constraint_bytes <= 2 * row
        # an entry that alone exceeds the budget still serves its
        # bringing request: resident as the single cache entry
        spec._CONSTRAINT_CACHE_MAX_BYTES = row // 2
        spec.compile_constraint({"type": "token_set", "allowed": [6]})
        assert len(spec._constraint_cache) == 1
    finally:
        del spec._CONSTRAINT_CACHE_MAX_BYTES
        spec._constraint_cache.clear()
        spec._constraint_bytes = 0


def test_constraint_cache_thread_safety(spec_pair):
    """Gateway HTTP threads validate constraints concurrently with the
    serve loop's admissions: hammered from four threads, the memo never
    raises (the unlocked LRU's pop-after-evict KeyError) and the byte
    accounting matches the resident entries exactly (no double-count
    from same-spec compile races)."""
    import threading

    spec, _, _ = spec_pair
    spec._constraint_cache.clear()
    spec._constraint_bytes = 0
    spec._CONSTRAINT_CACHE_MAX_BYTES = 4 * V * 4   # churn: ~4 entries
    errs = []

    def worker(i):
        try:
            for j in range(60):
                spec.compile_constraint(
                    {"type": "token_set",
                     "allowed": [2 + (i + j) % 10]})
        except Exception as e:          # pragma: no cover - the bug
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errs, errs
        assert spec._constraint_bytes == sum(
            c.mask_bytes() for c in spec._constraint_cache.values())
    finally:
        del spec._CONSTRAINT_CACHE_MAX_BYTES
        spec._constraint_cache.clear()
        spec._constraint_bytes = 0


def test_http_speculative_fields_and_load_validation(spec_pair):
    """The HTTP front end: /v1/generate carries constraint/speculate/
    draft_model (wrong draft name 400s), and /v1/models load refuses
    stray draft fields without draft_model instead of silently loading
    a plain group."""
    import json
    import urllib.error
    import urllib.request

    from paddle_tpu.serving.gateway import GatewayServer

    def post(addr, route, body):
        req = urllib.request.Request(
            f"http://{addr}{route}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=60)

    spec, target, _ = spec_pair
    seqs, src, lens = _sources(seed=21, n=1)
    ref = _trunc_at_end(target.greedy(src, lens, max_new=OUT,
                                      stop_at_end=False)[0])
    gw = Gateway(n_slots=2, max_new_tokens=OUT)
    gw.load_model("m", "1", instance=spec)
    srv = GatewayServer(gw)
    addr = srv.start()
    try:
        prompt = [int(t) for t in seqs[0]]
        out = json.loads(post(addr, "/v1/generate",
                              {"model": "m", "prompt": prompt,
                               "max_new": OUT}).read())
        assert out["tokens"] == ref
        allowed = {4, 5, 6}
        out_c = json.loads(post(
            addr, "/v1/generate",
            {"model": "m", "prompt": prompt, "max_new": OUT,
             "constraint": {"type": "token_set",
                            "allowed": sorted(allowed)}}).read())
        assert all(t in allowed | {END} for t in out_c["tokens"])
        with pytest.raises(urllib.error.HTTPError) as e:
            post(addr, "/v1/generate",
                 {"model": "m", "prompt": prompt,
                  "draft_model": "not-the-draft"})
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            post(addr, "/v1/models",
                 {"action": "load", "model": "x", "version": "1",
                  "draft_version": "1", "speculate_k": 2})
        assert e.value.code == 400
        assert "draft_model" in json.loads(
            e.value.read().decode())["error"]
        with pytest.raises(urllib.error.HTTPError) as e:   # swap too
            post(addr, "/v1/models",
                 {"action": "swap", "model": "x", "version": "1",
                  "speculate_k": 2})
        assert e.value.code == 400
    finally:
        srv.stop()
        gw.shutdown(drain=True)


def test_verify_program_cost_plan_clean(spec_pair):
    """The k-token verify program goes through the static cost analyzer
    without unregistered-cost-rule findings, and its plan charges the
    pool plus the K-wide activations/mask feed."""
    spec, _, _ = spec_pair
    from paddle_tpu.fluid.analysis.cost import plan_program

    prog = spec._verify[0]
    diags = prog.analyze(level="cost")
    assert not [f for f in diags.findings
                if f.code == "cost/unregistered-cost-rule"], \
        [str(f) for f in diags.findings]
    plan = plan_program(prog, assume_batch=4)
    assert plan.components.get("kv_pool", 0) > 0
    # the [lanes, K, vocab] mask is a real feed the plan must price
    plan1 = plan_program(spec._draft_prog[0], assume_batch=4)
    assert plan.peak_bytes > 0 and plan1.peak_bytes > 0
