"""A kind of layer whose cache is ONE pool (ISSUE 32: latent attention,
the values being the leading columns of the key row): what
``lm_pool_layout`` allocates, what ``kv_bytes_per_token``,
``cache_stats``, ``estimate_lm_hbm`` and the registry's cost say of it,
that a model of pool PAIRS is laid out as before, and the latent form of
the split paged-attention kernel (Pallas, interpreted) against the gather
form and against plain attention."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import split_walks
from paddle_tpu.kernels.flash_attention import (latent_row_width,
                                                ragged_decode_attention,
                                                split_query_tile)
from paddle_tpu.serving import PagedLMGenerator
from paddle_tpu.serving.gateway.registry import ModelRegistry
from paddle_tpu.serving.paged_lm import estimate_lm_hbm, lm_pool_layout
from perfbench.families import deepseek_v3 as fam
from perfbench.families import mimo_v2_flash as mimo

RNG = np.random.default_rng(576)


def _config(family, path, **over):
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    return family.serving({**cfg, **over})["manifest"]["config"]


def moonlight(**over):
    return _config(fam, "perfbench/configs/moonlight-16b-a3b-l5.json", **over)


def test_a_latent_kind_is_one_pool_at_the_published_widths():
    lay = lm_pool_layout(moonlight())
    (kind, g), = lay["groups"].items()
    spec = g["spec"]
    assert kind == "global" and spec.latent and spec.window is None
    assert (spec.q_heads, spec.kv_heads, spec.d_key, spec.d_value) == \
        (16, 1, 576, 512)
    assert spec.layers == (0, 1, 2, 3, 4)
    # ONE pool, 2049 pages x 5 layers of 256 tokens; a row is the 576
    # numbers [latent | rotary key] in whole lane tiles (640)
    assert "v" not in g and "v_shape" not in g
    assert g["k"] == "moon@kv_pool.global.k"
    assert g["k_shape"] == [2049 * 5, 256, 640]
    assert latent_row_width(576) == 640 and latent_row_width(512) == 512
    assert (g["table"], g["page_size"], g["decode_pages"]) == (32, 256, None)
    # a prefill chunk of 256 goes through the kernel in tiles of 64
    assert lay["tile"] == 64 == split_query_tile(256, 16, 1, 640, 512, 256,
                                                 2, latent=True)
    # a value pool beside it would take VMEM the one-pool form leaves free
    assert split_query_tile(256, 16, 1, 640, 512, 2048, 2, latent=True) \
        > split_query_tile(256, 16, 1, 640, 512, 2048, 2)


def test_what_is_reported_of_a_latent_kind_agrees_with_what_is_allocated():
    """Tiny sizes: the generator's pool in the scope, ``cache_stats``,
    ``kv_bytes_per_token``, the static plan and the registry's cost."""
    with open("perfbench/configs/moonlight-16b-a3b-l5.json",
              encoding="utf-8") as f:
        cfg = {**json.load(f), **fam.REHEARSAL["serve"]["cfg"]}
    conf = fam.serving(cfg)["manifest"]["config"]
    gen = PagedLMGenerator(**conf)
    g = gen.layout["groups"]["global"]
    pools = [n for n in gen.scope.vars if "@kv_pool" in n]
    assert pools == [g["k"]]                            # one, not a pair
    held = gen.scope.vars[g["k"]]
    assert list(held.shape) == g["k_shape"] == [29 * 3, 8, 128]
    stats = gen.cache_stats()["hbm"]
    assert stats["pool_bytes"] == {"global": held.size * 4}
    # 3 layers x a 128-wide float32 row (24 numbers in a whole lane tile)
    assert stats["kv_bytes_per_token"] == gen.kv_bytes_per_token() \
        == 3 * 128 * 4
    assert gen.counters()["kv_bytes_per_token"] == 3 * 128 * 4
    plan = estimate_lm_hbm(conf)
    cost, parts = ModelRegistry._estimate_cost_detail("lm_generator", None,
                                                      conf)
    assert cost == plan.peak_bytes and parts == dict(plan.components)
    # the plan holds the pool once: every persistable but the parameters
    shapes = gen.builder.param_shapes(gen.model, gen.prefix)
    params = sum(int(np.prod(s)) for s in shapes.values()) * 4
    assert params + held.size * 4 <= cost < params + 2 * held.size * 4
    assert gen.static_hbm_estimate().peak_bytes == cost


def test_a_model_of_pool_pairs_is_laid_out_as_before():
    lay = lm_pool_layout(_config(
        mimo, "perfbench/configs/mimo-v2-flash-ep32.json"))
    glob, win = lay["groups"]["global"], lay["groups"]["window"]
    assert glob["k_shape"] == [2113 * 2, 256, 4 * 192]
    assert glob["v_shape"] == [2113 * 2, 256, 4 * 128]
    assert win["k_shape"] == [257 * 5, 128, 8 * 192]
    assert win["v_shape"] == [257 * 5, 128, 8 * 128]
    assert (glob["k"], glob["v"]) == ("mimo@kv_pool.global.k",
                                      "mimo@kv_pool.global.v")
    assert not glob["spec"].latent and not win["spec"].latent
    assert lay["tile"] == 32
    # 2 global layers x (768 + 512) bfloat16 columns a token
    assert 2 * 2 * (glob["k_shape"][2] + glob["v_shape"][2]) == 5120


# -- the latent form of the kernel -------------------------------------------

def _plain(q, rows, q0, values):
    """q [C, H, dk] at positions q0.., rows [n, dk]: a loop a head; the
    values are the rows' leading ``values`` columns."""
    c, h, dk = q.shape
    out = np.zeros((c, h, values))
    for i in range(c):
        t = q0 + i
        for head in range(h):
            a = rows[:t + 1] @ q[i, head] / np.sqrt(dk)
            e = np.exp(a - a.max())
            out[i, head] = (e / e.sum()) @ rows[:t + 1, :values]
    return out


def _latent_case(h, dk, values, ps, c, contexts, n_layer=2, layer=1):
    b = len(contexts)
    logical = -(-max(contexts) // ps)
    width = latent_row_width(dk)
    pool = RNG.normal(size=((1 + b * logical) * n_layer, ps, width)) \
        .astype(np.float32)                 # junk where nothing was written
    table = np.zeros((b, logical), np.int32)
    kept, nxt = [], 1
    for lane, n in enumerate(contexts):
        rows = RNG.normal(size=(n, dk)).astype(np.float32)
        kept.append(rows)
        for page in range((n - 1) // ps + 1):
            table[lane, page] = nxt
            span = slice(page * ps, min(n, (page + 1) * ps))
            row = nxt * n_layer + layer
            pool[row, :span.stop - span.start, :dk] = rows[span]
            pool[row, :span.stop - span.start, dk:] = 0.0
            nxt += 1
    lengths = np.asarray(contexts, np.int32)
    q = RNG.normal(size=(b, c, h, dk)).astype(np.float32)
    want = np.stack([_plain(q[i], kept[i], contexts[i] - c, values)
                     for i in range(b)])
    return dict(q=q, pool=pool, table=table, lengths=lengths,
                base=lengths - c), want


@pytest.mark.parametrize("shape", [(4, 24, 16), (16, 576, 512)],
                         ids=["narrow", "published-row"])
@pytest.mark.parametrize("c", [1, 4], ids=["decode", "tile"])
def test_the_latent_kernel_matches_the_gather_form_and_plain_attention(
        shape, c):
    """One KV head of the row's width shared by every query head, values
    = the row's leading columns, contexts that end inside a page (37 and
    9 of 8-token pages) and on its edge (40)."""
    h, dk, values = shape
    f, want = _latent_case(h, dk, values, 8, c, contexts=[37, 9, 40])
    args = [jnp.asarray(f[k]) for k in ("q", "pool", "table", "lengths",
                                        "base")]
    kw = dict(layer=1, n_layer=2, latent_values=values,
              sm_scale=dk ** -0.5)
    xla = np.asarray(ragged_decode_attention(*args, impl="xla", **kw))
    got = np.asarray(ragged_decode_attention(*args, impl="pallas_interpret",
                                             **kw))
    assert got.shape == (3, c, h, values)
    np.testing.assert_allclose(xla, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, xla, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("group", ["derived", 1, 2, 4, 8])
@pytest.mark.parametrize("contexts, c", [
    ([256, 1, 8, 64, 129], 1),      # 32 slots: full, one token, the edges
    ([264, 200, 128, 3], 1),        # 33 slots: no multiple of any group
    ([30, 17, 32, 9], 1),           # 4 slots: narrower than a group of 8
    ([264, 64, 20], 4),             # a tile's queries over 33 slots
], ids=["32-slots", "33-slots", "4-slots", "33-slots-tile"])
def test_the_latent_kernel_walks_a_group_of_slots_like_a_slot_a_step(
        contexts, c, group, monkeypatch):
    """ISSUE 44, the latent form (no value pool: one copy a slot): a
    group of table slots a grid step gives the result of a slot a step
    bit for bit, the gather form's within rounding, and nothing for an
    idle lane."""
    f, want = _latent_case(4, 24, 16, 8, c, contexts=contexts + [50])
    f["lengths"][-1] = 0                          # an idle lane
    args = [jnp.asarray(f[k]) for k in ("q", "pool", "table", "lengths",
                                        "base")]
    kw = dict(layer=1, n_layer=2, latent_values=16, sm_scale=24 ** -0.5)
    got, one = split_walks(
        lambda: ragged_decode_attention(*args, impl="pallas_interpret",
                                        **kw), group, monkeypatch)
    xla = np.asarray(ragged_decode_attention(*args, impl="xla", **kw))
    assert np.array_equal(got, one)
    np.testing.assert_allclose(got, xla, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=2e-5, atol=2e-5)
    assert np.all(got[-1] == 0.0)


def test_a_dead_lane_of_the_latent_kernel_reads_nothing():
    f, want = _latent_case(4, 24, 16, 8, 1, contexts=[20, 5])
    f["lengths"][1] = 0                           # an idle lane
    for impl in ("xla", "pallas_interpret"):
        got = np.asarray(ragged_decode_attention(
            *[jnp.asarray(f[k]) for k in ("q", "pool", "table", "lengths",
                                          "base")],
            layer=1, n_layer=2, impl=impl, latent_values=16,
            sm_scale=24 ** -0.5))
        assert np.all(got[1] == 0.0)
        np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)


def test_the_latent_form_refuses_what_it_is_not():
    q = jnp.zeros((1, 1, 4, 24))
    pool = jnp.zeros((2, 8, 128))
    args = (q, pool, jnp.zeros((1, 1), jnp.int32), jnp.ones(1, jnp.int32),
            jnp.zeros(1, jnp.int32))
    with pytest.raises(ValueError, match="latent_values"):
        ragged_decode_attention(*args, layer=0, n_layer=1, impl="xla",
                                latent_values=16, v_pool=pool)
    with pytest.raises(ValueError, match="latent_values"):
        ragged_decode_attention(*args, layer=0, n_layer=1, impl="xla",
                                latent_values=32)      # wider than a query
