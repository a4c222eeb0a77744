"""The DeepSeek-V3 block (ISSUE 32: latent attention, a routed-expert layer
beside shared experts) at a small size on the CPU, seeded weights, each
part against the plain reference (``perfbench/reference/deepseek_v3.py``,
which imports nothing of the program and computes attention in the
EXPANDED form): the whole model, prefill then paged decode, through
``PagedLMGenerator`` and through the gateway; the absorbed form against
the expanded one on the same weights; the routed-expert op with
``routed_scale``; and the shares of a divided expert layer adding up."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fluid.ops.llm_ops import route_top_k
from paddle_tpu.kernels.flash_attention import ragged_decode_attention
from paddle_tpu.serving import PagedLMGenerator
from paddle_tpu.serving.gateway import Gateway, ModelRegistry
from perfbench import serve_cell, weights
from perfbench.families import deepseek_v3 as fam
from test_llm_ops import run_op

ref = fam.ref
SEED = 3200000032
RNG = np.random.default_rng(32)
F32 = lambda x: x                                        # noqa: E731


def tiny_cfg(**over):
    with open("perfbench/configs/moonlight-16b-a3b-l5.json",
              encoding="utf-8") as f:
        cfg = json.load(f)
    return {**cfg, **fam.REHEARSAL["serve"]["cfg"], **over}


def make_generator(cfg, seed=SEED, **over):
    conf = dict(fam.serving(cfg)["manifest"]["config"], **over)
    gen = PagedLMGenerator(**conf)
    gen.load_weights(weights.make(
        fam.param_shapes(cfg, cfg["param_prefix"]), seed,
        kind_of=fam.leaf_kind))
    gen.open_slots(conf["lanes"])
    return gen


def reference_logits(cfg, prompts, outputs, seed=SEED):
    seqs = [np.asarray(list(p) + list(o[:-1]), np.int32)
            for p, o in zip(prompts, outputs)]
    logits, _ = ref.forward_logits(
        lambda shapes: weights.make(shapes, seed, kind_of=fam.leaf_kind),
        cfg["param_prefix"], cfg, seqs, [len(o) for o in outputs])
    return [np.asarray(x) for x in logits]


@pytest.mark.parametrize("impl", [None, "pallas_interpret"],
                         ids=["xla", "pallas-interpret"])
def test_step_logits_equal_the_reference_full_forward(impl):
    """The float32 logits behind every emitted token, prefill (two chunks
    a step beside decoding lanes, prompts shorter and longer than a chunk
    and a page) then paged decode against the one latent pool, equal the
    reference's full forward in the expanded form.  Tolerance: both sides
    compute in float32 on the CPU (the artifact's type here), and differ
    by the order of their sums and by the absorbed products' rounding:
    5e-6 was read; rtol 1e-4 with atol 2e-5 is ``test_paged_lm.py``'s."""
    cfg = tiny_cfg()
    gen = make_generator(cfg, attn_impl=impl)
    assert gen.tile == cfg["chunk_size"] == 8
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, 64, n).tolist() for n in (5, 23, 40, 9)]
    new = [6, 16, 10, 12]
    for slot, (p, m) in enumerate(zip(prompts, new)):
        gen.admit_slot(slot, p, max_new=m)
    outs, logits = [[] for _ in new], [[] for _ in new]
    for _ in range(100):
        emitted, lg = gen.step_logits()
        for slot, tok in emitted.items():
            outs[slot].append(tok)
            logits[slot].append(lg[slot])
            if len(outs[slot]) == new[slot]:
                gen.clear_slot(slot)
        if all(len(o) == n for o, n in zip(outs, new)):
            break
    assert [len(o) for o in outs] == new
    for want, got in zip(reference_logits(cfg, prompts, outs), logits):
        np.testing.assert_allclose(np.stack(got), want, rtol=1e-4, atol=2e-5)
    counted = gen.counters()
    # a row a token a layer, into the one pool: prompts and all decoded
    # tokens but each request's last (emitted, never written)
    assert counted["latent_rows_written"] == 3 * (
        sum(map(len, prompts)) + sum(new) - len(new))
    assert counted["kv_bytes_per_token"] == gen.kv_bytes_per_token()
    assert counted["moe_pairs_here"] > 0
    assert counted["global_pages_in_use"] == 0


def test_the_whole_model_through_the_gateway_follows_the_reference(tmp_path):
    """Registry artifact (float32 masters) -> Gateway.load_model (the
    builder found by the published ``model_type``) -> scheduler ->
    lane_step: greedy tokens equal the reference's argmax, teacher-forced
    through its full forward (no cache, no paging, no absorption)."""
    cfg = tiny_cfg()
    root = str(tmp_path / "models")
    serve_cell.write_artifact(cfg, SEED, root)
    gw = Gateway(registry=ModelRegistry(root=root), n_slots=cfg["n_slots"],
                 max_new_tokens=cfg["max_out_len"])
    key = gw.load_model(cfg["param_prefix"], serve_cell.VERSION)
    inst = gw.registry.instance(key)
    assert isinstance(inst, PagedLMGenerator)
    assert inst.builder.__name__ == "paddle_tpu.models.deepseek_v3"
    assert gw.registry.entries()[0]["kind"] == "lm_generator"
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, 64, n).tolist() for n in (3, 8, 17, 40, 9, 31)]
    new = [5, 16, 9, 12, 16, 7]
    gw.serve()
    try:
        reqs = [gw.submit(cfg["param_prefix"], p, max_new=m)
                for p, m in zip(prompts, new)]
        for r in reqs:
            assert r.wait(120) and r.error is None
    finally:
        gw.shutdown(drain=True)
    outputs = [list(r.tokens) for r in reqs]
    assert [len(o) for o in outputs] == new
    for lg, out in zip(reference_logits(cfg, prompts, outputs), outputs):
        gap = lg.max(axis=-1) - lg[np.arange(len(out)), out]
        assert gap.max() < 1e-4, gap.max()
    stats = gw.sched.stats()["engine"]
    assert stats["moe_pairs_here"] > 0 and stats["latent_rows_written"] > 0
    assert stats["global_pages_in_use"] == 0       # every page came back


def test_what_the_builder_does_not_build_it_refuses():
    from paddle_tpu.models import deepseek_v3 as M

    cfg = tiny_cfg()
    assert M.config_from_dict(cfg).routed_scaling_factor == 2.446
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn", "factor": 40})):
        with pytest.raises(NotImplementedError, match=key):
            M.config_from_dict({**cfg, key: value})


# -- the absorbed form against the expanded form -----------------------------

def test_absorbed_attention_equals_expanded_attention():
    """One layer's attention on the same weights, both ways: the
    reference expands every position's latent to per-head keys and values;
    the program folds W_UK into the query, attends against the latent rows
    [c | k_r] themselves (values = their leading columns) and applies W_UV
    to the result."""
    h, rank, nope, rope, dv, t = 4, 16, 12, 8, 12, 19
    q = RNG.normal(size=(t, h, nope + rope)).astype(np.float32)
    latent = RNG.normal(size=(t, rank)).astype(np.float32)
    k_r = RNG.normal(size=(t, rope)).astype(np.float32)
    kvb = (RNG.normal(size=(rank, h * (nope + dv))) * rank ** -0.5) \
        .astype(np.float32)
    # expanded (positions already rotated: rotation commutes with neither
    # form's products)
    kv = (latent @ kvb).reshape(t, h, nope + dv)
    k = np.concatenate([kv[..., :nope],
                        np.broadcast_to(k_r[:, None], (t, h, rope))], -1)
    a = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(nope + rope)
    a = np.where(np.tril(np.ones((t, t), bool))[None], a, -np.inf)
    p = np.exp(a - a.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", p, kv[..., nope:]).reshape(t, h * dv)
    # absorbed, through the ops and one page of a pool
    q_abs = run_op("latent_absorb", {"X": q[..., :nope], "W": kvb},
                   {"side": "query", "d_nope": nope})["Out"]
    assert q_abs.shape == (t, h, rank)
    rows = np.concatenate([latent, k_r], axis=1)             # [t, 24]
    pool = np.zeros((2, 32, 128), np.float32)
    pool[1, :t, :rank + rope] = rows
    qq = np.concatenate([q_abs, q[..., nope:]], axis=-1)
    ctx = ragged_decode_attention(
        jnp.asarray(qq[None]), jnp.asarray(pool), jnp.ones((1, 1), jnp.int32),
        jnp.asarray([t], jnp.int32), jnp.zeros(1, jnp.int32), layer=0,
        n_layer=1, impl="xla", latent_values=rank,
        sm_scale=(nope + rope) ** -0.5)
    got = run_op("latent_absorb", {"X": np.asarray(ctx)[0], "W": kvb},
                 {"side": "output", "d_nope": nope})["Out"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the routed-expert layer with a scale and shared experts -----------------

CFG = {"num_hidden_layers": 2, "hidden_size": 32, "num_attention_heads": 4,
       "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 8,
       "v_head_dim": 12, "vocab_size": 64, "first_k_dense_replace": 1,
       "moe_layer_freq": 1, "n_routed_experts": 64, "n_shared_experts": 2,
       "num_experts_per_tok": 6, "routed_scaling_factor": 2.446,
       "intermediate_size": 64, "moe_intermediate_size": 16,
       "rope_theta": 50000, "rms_norm_eps": 1e-5}


def _expert_layer(n_experts=64, d=32, f=16, shared=2, tokens=24):
    w = {"router.w": RNG.normal(size=(d, n_experts)) * d ** -0.5,
         "router.bias": 0.02 * RNG.normal(size=n_experts),
         "gate": RNG.normal(size=(n_experts, d, f)) * d ** -0.5,
         "up": RNG.normal(size=(n_experts, d, f)) * d ** -0.5,
         "down": RNG.normal(size=(n_experts, f, d)) * f ** -0.5,
         "shared.gate": RNG.normal(size=(d, shared * f)) * d ** -0.5,
         "shared.up": RNG.normal(size=(d, shared * f)) * d ** -0.5,
         "shared.down": RNG.normal(size=(shared * f, d))
         * (shared * f) ** -0.5}
    x = RNG.normal(size=(tokens, d))
    return {k: v.astype(np.float32) for k, v in w.items()}, \
        x.astype(np.float32)


def _share(w, x, first, held, impl="xla", **attrs):
    inputs = {
        "X": x, "RouterW": w["router.w"], "RouterBias": w["router.bias"],
        "WGate": w["gate"][first:first + held],
        "WUp": w["up"][first:first + held],
        "WDown": w["down"][first:first + held]}
    return run_op("routed_experts", inputs,
                  {"top_k": 6, "first_expert": first, "impl": impl, **attrs})


def _reference_layer(w, x, first, held, shared):
    z = ref.sizes({**CFG, "n_routed_experts": held, "first_expert": first,
                   "published": {"n_routed_experts": 64}})
    names = {"p.moe.router.w": w["router.w"],
             "p.moe.router.bias": w["router.bias"],
             "p.moe.experts.gate.w": w["gate"][first:first + held],
             "p.moe.experts.up.w": w["up"][first:first + held],
             "p.moe.experts.down.w": w["down"][first:first + held],
             "p.moe.shared.gate.w": w["shared.gate"],
             "p.moe.shared.up.w": w["shared.up"],
             "p.moe.shared.down.w": w["shared.down"]}
    with jax.default_matmul_precision("highest"):
        out, _ = ref.moe(F32, {k: jnp.asarray(v) for k, v in names.items()},
                         "p", jnp.asarray(x), z, shared=shared)
    return np.asarray(out)


def _shared(w, x):
    return run_op("gated_ffn", {"X": x, "WGate": w["shared.gate"],
                                "WUp": w["shared.up"],
                                "WDown": w["shared.down"]},
                  {"scope": "moe/shared"})["Out"]


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_a_whole_layer_with_its_scale_against_a_dense_loop(impl):
    """All 64 experts held (``experts_held == n_routed_experts``), 6 a
    token, weights 2.446 x score over the selected scores' sum, plus the
    shared experts: the reference's loop over every expert."""
    w, x = _expert_layer()
    got = _share(w, x, 0, 64, impl=impl, routed_scale=2.446)
    np.testing.assert_allclose(
        got["Out"] + _shared(w, x), _reference_layer(w, x, 0, 64, True),
        rtol=2e-4, atol=3e-5)
    assert int(got["Load"].sum()) == x.shape[0] * 6     # every pair, here
    # the scale is no rounding: dropping it shows
    bare = _share(w, x, 0, 64, impl=impl)
    np.testing.assert_allclose(2.446 * bare["Out"], got["Out"], rtol=1e-5,
                               atol=1e-6)
    assert np.abs(bare["Out"] - got["Out"]).max() > 0.1


def test_without_a_scale_the_weights_are_the_parents_bit_for_bit():
    """``routed_scale`` absent: scores over the selected scores' sum and
    nothing else, as before this op took a scale; present at 1.0 the same
    values (1e-20 is under float32's rounding of a sum of sigmoids)."""
    w, x = _expert_layer()
    idx, weight = route_top_k(jnp.asarray(x), jnp.asarray(w["router.w"]),
                              jnp.asarray(w["router.bias"]), 6)
    scores = jax.nn.sigmoid(jnp.matmul(
        jnp.asarray(x), jnp.asarray(w["router.w"]),
        precision=jax.lax.Precision.HIGHEST))
    _, want_idx = jax.lax.top_k(scores + jnp.asarray(w["router.bias"]), 6)
    sel = jnp.take_along_axis(scores, want_idx, axis=-1)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    assert np.array_equal(np.asarray(weight),
                          np.asarray(sel / jnp.sum(sel, -1, keepdims=True)))
    bare, one = _share(w, x, 0, 64), _share(w, x, 0, 64, routed_scale=1.0)
    assert np.array_equal(bare["Out"], one["Out"])


def test_the_8_shares_and_the_shared_experts_once_add_up_to_the_layer():
    """Eight chips share the layer, 8 experts each (``experts_held`` 8,
    ``first_expert`` 0, 8 .. 56): what each computes for the tokens routed
    to it, and the shared experts counted ONCE, sum to the whole layer the
    uncut reference gives."""
    w, x = _expert_layer()
    parts = [_share(w, x, first, 8, routed_scale=2.446)
             for first in range(0, 64, 8)]
    whole = _reference_layer(w, x, 0, 64, True)
    np.testing.assert_allclose(
        sum(p["Out"] for p in parts) + _shared(w, x), whole,
        rtol=2e-4, atol=3e-5)
    assert sum(int(p["Load"].sum()) for p in parts) == x.shape[0] * 6
    # and each share is the reference's share, without the shared experts
    np.testing.assert_allclose(parts[3]["Out"],
                               _reference_layer(w, x, 24, 8, False),
                               rtol=2e-4, atol=3e-5)
