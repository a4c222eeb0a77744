"""The ops of a decoder-only block (ISSUE 28) at a small size on the CPU,
each against the plain reference (``perfbench/reference/mimo_v2_flash.py``,
which imports nothing of the program): RMSNorm, partial rotary embedding at
both bases, the gated feed-forward, window + sink + grouped-KV attention
through pages in its Pallas (interpret) and XLA forms, the routed-expert
op, and that the 32 shares of an expert layer add up to the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import split_walks
from paddle_tpu.fluid.core.registry import EmitCtx, get_op_info
from paddle_tpu.kernels.flash_attention import (_head_slices,
                                                ragged_decode_attention,
                                                split_query_tile,
                                                split_slot_group)
from paddle_tpu.kernels.grouped_matmul import grouped_matmul
from perfbench.reference import mimo_v2_flash as ref

RNG = np.random.default_rng(28)
F32 = lambda x: x                                        # noqa: E731


class _Op:
    def __init__(self, attrs):
        self.attrs = attrs


def run_op(op_type, ins, attrs=None):
    """Emit one registered op on arrays: slot -> array in, slot -> array
    out."""
    out = get_op_info(op_type).emit(
        EmitCtx(_Op(attrs or {}), mode="infer"),
        {k: [jnp.asarray(v)] for k, v in ins.items()})
    return {k: np.asarray(v[0]) for k, v in out.items()}


def test_rms_norm_matches_the_reference():
    x = RNG.normal(size=(7, 32)).astype(np.float32) * 3
    g = (1 + 0.1 * RNG.normal(size=32)).astype(np.float32)
    got = run_op("rms_norm", {"X": x, "Scale": g}, {"epsilon": 1e-5})["Out"]
    np.testing.assert_allclose(got, ref.rms_norm(x, g, 1e-5), rtol=1e-6,
                               atol=1e-6)
    low = run_op("rms_norm", {"X": x, "Scale": g},
                 {"epsilon": 1e-5, "out_dtype": "bfloat16"})["Out"]
    assert low.dtype == jnp.bfloat16


@pytest.mark.parametrize("base", [5e6, 1e4])
def test_partial_rotary_matches_the_reference(base):
    x = RNG.normal(size=(9, 4, 24)).astype(np.float32)
    pos = np.asarray([0, 1, 2, 3, 100, 1000, 8000, 8447, 5], np.int32)
    got = run_op("rotary_embedding", {"X": x, "Pos": pos},
                 {"rotary_dim": 8, "base": base})["Out"]
    np.testing.assert_allclose(got, ref.rotary(jnp.asarray(x),
                                               jnp.asarray(pos), 8, base),
                               rtol=1e-5, atol=1e-5)
    # the 16 dims past the rotated 8 pass, and position 0 rotates nothing
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)
    assert np.abs(got[5, :, :8] - x[5, :, :8]).max() > 0.1


def test_gated_ffn_matches_the_reference():
    x = RNG.normal(size=(6, 16)).astype(np.float32)
    wg, wu = (RNG.normal(size=(16, 40)).astype(np.float32) for _ in "gu")
    wd = RNG.normal(size=(40, 16)).astype(np.float32)
    hid = run_op("swiglu", {"Gate": x @ wg, "Up": x @ wu})["Out"]
    np.testing.assert_allclose(hid @ wd, ref.gated_ffn(F32, x, wg, wu, wd),
                               rtol=2e-5, atol=2e-5)


# -- attention through pages ---------------------------------------------------

def _plain_attention(q, keys, values, q0, window, sink, hkv):
    """q [C, H, dk] at positions q0.., keys [n, hkv, dk]: section 1's
    equations, a loop a head."""
    c, h, dk = q.shape
    g = h // hkv
    out = np.zeros((c, h, values.shape[-1]))
    for i in range(c):
        t = q0 + i
        lo = 0 if window is None else max(0, t - window + 1)
        for head in range(h):
            a = keys[lo:t + 1, head // g] @ q[i, head] / np.sqrt(dk)
            m = a.max() if sink is None else max(a.max(), sink[head])
            e = np.exp(a - m)
            den = e.sum() + (0 if sink is None else np.exp(sink[head] - m))
            out[i, head] = (e / den) @ values[lo:t + 1, head // g]
    return out


def _paged_case(h, hkv, dk, dv, ps, window, ring, c, contexts, n_layer=2,
                layer=1):
    """Lanes whose contexts lie in pages (a ring of them for a window
    layer), the last ``c`` positions of each being the queries."""
    b = len(contexts)
    logical = -(-max(contexts) // ps)
    width = (-(-(c + window - 2) // ps) + 1) if ring else logical
    rows = (1 + b * logical) * n_layer
    kp = RNG.normal(size=(rows, ps, hkv * dk)).astype(np.float32)
    vp = RNG.normal(size=(rows, ps, hkv * dv)).astype(np.float32)
    table = np.zeros((b, width), np.int32)
    keys, values, nxt = [], [], 1
    for lane, n in enumerate(contexts):
        k = RNG.normal(size=(n, hkv, dk)).astype(np.float32)
        v = RNG.normal(size=(n, hkv, dv)).astype(np.float32)
        keys.append(k), values.append(v)
        first = 0 if not ring else max(0, (n - c - window + 1) // ps)
        for page in range(first, (n - 1) // ps + 1):
            table[lane, page % width if ring else page] = nxt
            row = nxt * n_layer + layer
            span = slice(page * ps, min(n, (page + 1) * ps))
            kp[row, :span.stop - span.start] = k[span].reshape(-1, hkv * dk)
            vp[row, :span.stop - span.start] = v[span].reshape(-1, hkv * dv)
            nxt += 1
    lengths = np.asarray(contexts, np.int32)
    q = RNG.normal(size=(b, c, h, dk)).astype(np.float32)
    sink = RNG.normal(size=h).astype(np.float32) if window else None
    feeds = dict(q=q, kp=kp, vp=vp, table=table, lengths=lengths,
                 base=lengths - c, top=(lengths - 1) // ps, sink=sink)
    want = np.stack([_plain_attention(q[i], keys[i], values[i],
                                      contexts[i] - c, window, sink, hkv)
                     for i in range(b)])
    return feeds, want


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("shape", [(8, 2, 24, 16), (8, 4, 192, 128)],
                         ids=["narrow", "published-head-widths"])
@pytest.mark.parametrize("window, ring, c", [(None, False, 1),
                                             (None, False, 4),
                                             (16, True, 1), (16, True, 8)],
                         ids=["global-decode", "global-chunk",
                              "window-decode", "window-chunk"])
def test_split_pool_attention_matches_plain_attention(impl, shape, window,
                                                      ring, c):
    """Grouped KV heads, keys wider than values, a window, a sink and a
    ring of pages, in both forms of the ragged kernel."""
    h, hkv, dk, dv = shape
    f, want = _paged_case(h, hkv, dk, dv, 8, window, ring, c,
                          contexts=[37, 9, 70])
    got = ragged_decode_attention(
        jnp.asarray(f["q"]), jnp.asarray(f["kp"]), jnp.asarray(f["table"]),
        jnp.asarray(f["lengths"]), jnp.asarray(f["base"]), layer=1,
        n_layer=2, impl=impl, v_pool=jnp.asarray(f["vp"]), window=window,
        sink=None if f["sink"] is None else jnp.asarray(f["sink"]),
        ring_top=jnp.asarray(f["top"]) if ring else None)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_a_dead_lane_reads_nothing_and_the_sink_takes_mass():
    f, want = _paged_case(4, 2, 24, 16, 8, 16, True, 1, contexts=[20, 5])
    f["lengths"][1] = 0                           # an idle lane
    for impl in ("xla", "pallas_interpret"):
        kw = dict(layer=1, n_layer=2, impl=impl, v_pool=jnp.asarray(f["vp"]),
                  window=16, ring_top=jnp.asarray(f["top"]))
        args = [jnp.asarray(f[k]) for k in ("q", "kp", "table", "lengths",
                                            "base")]
        got = np.asarray(ragged_decode_attention(
            *args, sink=jnp.asarray(f["sink"]), **kw))
        assert np.all(got[1] == 0.0)
        np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
        bare = np.asarray(ragged_decode_attention(*args, sink=None, **kw))
        assert np.abs(bare[0] - got[0]).max() > 1e-3    # dropping it shows


# id: (window, ring, c, contexts, the table's width): 8-token pages; the
# width follows from the longest context, or from window and c in a ring
_WALKS = {
    # a full table, one token, a page's edge (8), a group of 8's edge (64)
    "global-32": (None, False, 1, [256, 1, 8, 64, 129], 32),
    "global-33": (None, False, 1, [264, 200, 128, 3], 33),
    "global-66": (None, False, 1, [528, 65, 192, 40], 66),
    "global-33-chunk": (None, False, 4, [264, 64, 20], 33),
    # a window over whole tables: the live slots are a run in the middle
    "window-33": (40, False, 1, [264, 100, 30], 33),
    # a window inside one page: the first lane's only live page is its
    # table's LAST slot, and the next lane's first group is dead after it
    "window-33-last-slot": (6, False, 1, [264, 100, 30], 33),
    # rings: wrapped (300 tokens through 10 slots), exactly full, short
    "ring-10": (73, True, 1, [300, 80, 9, 72], 10),
    "ring-4": (25, True, 1, [100, 32, 1, 24], 4),
    "ring-10-chunk": (66, True, 8, [300, 81, 16], 10),
}


@pytest.mark.parametrize("group", ["derived", 1, 2, 4, 8])
@pytest.mark.parametrize("walk", list(_WALKS))
def test_a_group_of_slots_walks_like_a_slot_a_step(walk, group, monkeypatch):
    """ISSUE 44: a grid step of the split kernel takes ``group`` table
    slots.  Whatever the group (the rule's own, 1, 2, 4, 8: multiples of
    the table's width and not, wider than a ring of 4), the result is that
    of a slot a step BIT FOR BIT (the slots are folded in slot order), and
    the gather form's and plain attention's within rounding; an idle lane
    (length 0) reads nothing."""
    window, ring, c, contexts, width = _WALKS[walk]
    f, want = _paged_case(4, 2, 24, 16, 8, window, ring, c,
                          contexts=contexts + [50])
    assert f["table"].shape[1] == width
    f["lengths"][-1] = 0                          # an idle lane
    args = [jnp.asarray(f[k]) for k in ("q", "kp", "table", "lengths",
                                        "base")]
    kw = dict(layer=1, n_layer=2, v_pool=jnp.asarray(f["vp"]),
              window=window,
              sink=None if f["sink"] is None else jnp.asarray(f["sink"]),
              ring_top=jnp.asarray(f["top"]) if ring else None)
    got, one = split_walks(
        lambda: ragged_decode_attention(*args, impl="pallas_interpret",
                                        **kw), group, monkeypatch)
    xla = np.asarray(ragged_decode_attention(*args, impl="xla", **kw))
    assert np.array_equal(got, one)
    np.testing.assert_allclose(got, xla, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=2e-5, atol=2e-5)
    assert np.all(got[-1] == 0.0)


# (queries, query heads, KV heads, key width, value width, page, slots,
#  latent) at the three served models' published widths, bfloat16
_CALLS = {
    "moonlight-latent": (16, 1, 640, 512, 256, 32, True, 64),
    "trinity-global": (32, 4, 128, 128, 256, 66, False, 64),
    "trinity-ring": (32, 4, 128, 128, 256, 10, False, 64),
    "mimo-global": (64, 4, 192, 128, 256, 33, False, 32),
    "mimo-ring": (64, 8, 192, 128, 128, 4, False, 32),
}


@pytest.mark.parametrize("call", list(_CALLS))
def test_the_slot_group_follows_from_the_calls_shapes(call):
    """``split_slot_group`` beside ``split_query_tile``: a decode row
    takes several slots a grid step, the prefill tile that
    ``split_query_tile`` derives takes one (its grid is a page a step, as
    before); a power of two, at most the table's width, within the fast
    memory it is given."""
    h, hkv, dk, dv, ps, slots, latent, tile = _CALLS[call]
    assert split_query_tile(256, h, hkv, dk, dv, ps, 2, latent=latent) \
        == tile
    decode = split_slot_group(1, h, hkv, dk, dv, ps, 2, slots, latent=latent)
    assert decode == {"moonlight-latent": 8}.get(call, 4)
    assert split_slot_group(tile, h, hkv, dk, dv, ps, 2, slots,
                            latent=latent) == 1
    # more fast memory, more slots, never more than the table holds
    more = split_slot_group(1, h, hkv, dk, dv, ps, 2, slots, latent=latent,
                            vmem_bytes=1 << 40)
    assert decode <= more <= slots and more & (more - 1) == 0
    assert 2 * more > slots
    assert split_slot_group(1, h, hkv, dk, dv, ps, 2, slots, latent=latent,
                            vmem_bytes=0) == 1


def test_head_slices_are_tile_aligned_at_the_published_widths():
    starts, width, offs = _head_slices(4, 192)
    assert (starts, width, offs) == ([0, 128, 384, 512], 256, [0, 64, 0, 64])
    assert _head_slices(8, 128) == ([i * 128 for i in range(8)], 128,
                                    [0] * 8)
    assert _head_slices(2, 24) == ([0, 0], 48, [0, 24])


@pytest.mark.parametrize("sizes", [[10, 0, 33, 7], [0, 0, 0, 0],
                                   [128, 0, 100, 28]])
def test_grouped_matmul_forms_agree(sizes):
    lhs = RNG.normal(size=(256, 64)).astype(np.float32)
    rhs = RNG.normal(size=(4, 64, 32)).astype(np.float32)
    want, at = np.zeros((256, 32), np.float32), 0
    for g, n in enumerate(sizes):
        want[at:at + n] = lhs[at:at + n] @ rhs[g]
        at += n
    for impl in ("xla", "pallas_interpret"):
        got = grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                             jnp.asarray(sizes, jnp.int32), impl=impl)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4)


# -- the routed-expert layer --------------------------------------------------

CFG = {"num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 4,
       "head_dim": 24, "v_head_dim": 16, "vocab_size": 64,
       "hybrid_layer_pattern": [0, 1], "moe_layer_freq": [0, 1],
       "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
       "partial_rotary_factor": 0.334, "n_routed_experts": 1,
       "first_expert": 0, "num_experts_per_tok": 8, "intermediate_size": 64,
       "moe_intermediate_size": 16, "rope_theta": 5e6,
       "swa_rope_theta": 1e4, "sliding_window": 8,
       "attention_value_scale": 0.707, "layernorm_epsilon": 1e-5,
       "published": {"n_routed_experts": 32}}


def _expert_layer(n_experts=32, d=32, f=16, tokens=24):
    w = {"router.w": RNG.normal(size=(d, n_experts)) * d ** -0.5,
         "router.bias": 0.02 * RNG.normal(size=n_experts),
         "gate": RNG.normal(size=(n_experts, d, f)) * d ** -0.5,
         "up": RNG.normal(size=(n_experts, d, f)) * d ** -0.5,
         "down": RNG.normal(size=(n_experts, f, d)) * f ** -0.5}
    x = RNG.normal(size=(tokens, d))
    return {k: v.astype(np.float32) for k, v in w.items()}, \
        x.astype(np.float32)


def _share(w, x, first, held, impl="xla", live=None):
    inputs = {
        "X": x, "RouterW": w["router.w"], "RouterBias": w["router.bias"],
        "WGate": w["gate"][first:first + held],
        "WUp": w["up"][first:first + held],
        "WDown": w["down"][first:first + held]}
    if live is not None:
        inputs["Live"] = np.asarray(live, np.int32)
    return run_op("routed_experts", inputs,
                  {"top_k": 8, "first_expert": first, "impl": impl})


def _reference_share(w, x, first, held):
    z = ref.sizes({**CFG, "n_routed_experts": held, "first_expert": first})
    names = {"p.moe.router.w": w["router.w"],
             "p.moe.router.bias": w["router.bias"],
             "p.moe.experts.gate.w": w["gate"][first:first + held],
             "p.moe.experts.up.w": w["up"][first:first + held],
             "p.moe.experts.down.w": w["down"][first:first + held]}
    with jax.default_matmul_precision("highest"):
        out, _ = ref.moe(F32, {k: jnp.asarray(v) for k, v in names.items()},
                         "p", jnp.asarray(x), z)
    return np.asarray(out)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_routed_experts_compute_their_own_part(impl):
    w, x = _expert_layer()
    got = _share(w, x, first=8, held=8, impl=impl)
    np.testing.assert_allclose(got["Out"], _reference_share(w, x, 8, 8),
                               rtol=2e-4, atol=2e-5)
    # no capacity: every pair whose expert is held is counted and computed
    z = ref.sizes(CFG)
    idx, _, _ = ref.route(F32, {"p.moe.router.w": jnp.asarray(w["router.w"]),
                                "p.moe.router.bias":
                                    jnp.asarray(w["router.bias"])},
                          "p", jnp.asarray(x), z)
    idx = np.asarray(idx)
    assert got["Load"].tolist() == [int(np.sum(idx == 8 + j))
                                    for j in range(8)]
    assert 0 < got["Load"].sum() < idx.size         # some here, most absent


def test_the_32_shares_add_up_to_the_uncut_layer():
    """One expert a share, 32 shares: what each computes for the tokens
    routed to it sums to the whole layer the reference gives with all 32
    experts held."""
    w, x = _expert_layer()
    parts = [_share(w, x, first=e, held=1) for e in range(32)]
    whole = _reference_share(w, x, 0, 32)
    np.testing.assert_allclose(sum(p["Out"] for p in parts), whole,
                               rtol=2e-4, atol=3e-5)
    # every token's 8 pairs are computed exactly once across the shares
    assert sum(int(p["Load"].sum()) for p in parts) == x.shape[0] * 8
    # and a share that holds experts nobody chose adds nothing
    quiet = [e for e, p in enumerate(parts) if p["Load"].sum() == 0]
    for e in quiet:
        assert np.all(parts[e]["Out"] == 0.0)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_rows_of_no_request_make_no_pair(impl):
    """``Live`` marks a request's tokens: the other rows (idle lanes, a
    chunk's padding) are counted in no expert's load, come out zero, and
    leave the live rows' result as it was."""
    w, x = _expert_layer()
    live = np.zeros(x.shape[0], np.int32)
    live[[0, 3, 4, 11, 23]] = 1
    whole = _share(w, x, first=8, held=8, impl=impl)
    got = _share(w, x, first=8, held=8, impl=impl, live=live)
    rows = live.astype(bool)
    np.testing.assert_allclose(got["Out"][rows], whole["Out"][rows],
                               rtol=1e-5, atol=1e-6)
    assert np.all(got["Out"][~rows] == 0.0)
    only = _share(w, x[rows], first=8, held=8, impl=impl)
    assert got["Load"].tolist() == only["Load"].tolist()
    assert 0 < got["Load"].sum() < whole["Load"].sum()
    dead = _share(w, x, first=8, held=8, impl=impl, live=0 * live)
    assert dead["Load"].sum() == 0 and np.all(dead["Out"] == 0.0)


def test_a_worst_case_share_drops_nothing():
    """Every token routed to every held expert (a selection bias that
    forces it): all T x 8 pairs are held here, none is dropped."""
    w, x = _expert_layer()
    w["router.bias"][:8] += 10.0
    got = _share(w, x, first=0, held=8)
    assert got["Load"].tolist() == [x.shape[0]] * 8
    np.testing.assert_allclose(got["Out"], _reference_share(w, x, 0, 8),
                               rtol=2e-4, atol=3e-5)
