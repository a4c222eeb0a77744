"""Arcee's Trinity block (ISSUE 42; ``model_type`` ``afmoe``: QK-normed,
gated attention with a rotary window in three layers of four and a
position-free global layer in the fourth, four norms a layer, sigmoid-routed
experts beside a shared one) at a small size on the CPU, seeded weights,
float32, against the plain reference (``perfbench/reference/afmoe.py``,
which imports nothing of the program): the served path one step ahead
(chunked prefill of prompts longer than the window, then decode through
both caches) on LOGITS; a program that leaves a part of the block out, or
gives the global layers a position, does not agree; the model through the
gateway; and the eight shares of a divided expert layer add up."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.serving import PagedLMGenerator
from paddle_tpu.serving.gateway import Gateway, ModelRegistry
from perfbench import serve_cell, weights
from perfbench.families import afmoe as fam
from test_llm_ops import run_op

ref = fam.ref
SEED = 4200000042
RNG = np.random.default_rng(42)
F32 = lambda x: x                                        # noqa: E731
PROMPTS, NEW = (5, 23, 40, 9), (6, 16, 10, 12)   # the window is 8 tokens


def tiny_cfg(**over):
    with open("perfbench/configs/trinity-mini-ep8-l8.json",
              encoding="utf-8") as f:
        cfg = json.load(f)
    return {**cfg, **fam.REHEARSAL["serve"]["cfg"], **over}


@functools.lru_cache(maxsize=None)
def tiny_weights():
    """Every leaf of the tiny model, made once and kept on the host (a
    generator's step donates what is in its scope): a leaf's values depend
    on the seed, its name and its shape alone, so the generator and the
    reference (which asks layer by layer) take theirs from one dict."""
    cfg = tiny_cfg()
    made = weights.make(fam.param_shapes(cfg, cfg["param_prefix"]), SEED,
                        kind_of=fam.leaf_kind)
    return {name: np.asarray(value) for name, value in made.items()}


def make_generator(cfg, **over):
    conf = dict(fam.serving(cfg)["manifest"]["config"], **over)
    gen = PagedLMGenerator(**conf)
    gen.load_weights(tiny_weights())
    gen.open_slots(conf["lanes"])
    return gen


def reference_logits(cfg, prompts, outputs):
    seqs = [np.asarray(list(p) + list(o[:-1]), np.int32)
            for p, o in zip(prompts, outputs)]
    logits, _ = ref.forward_logits(
        lambda shapes: {name: tiny_weights()[name] for name in shapes},
        cfg["param_prefix"], cfg, seqs, [len(o) for o in outputs])
    return [np.asarray(x) for x in logits]


def serve_ahead(gen):
    """The script's four requests through ``lane_step_ahead``; every
    launch keeps its logits on the device.  -> (prompts, tokens, logits)
    per request."""
    flights, launch = [], gen._launch

    def keeping_logits(want_logits=False):
        launch(True)
        flights.append(gen._in_flight[-1])

    gen._launch = keeping_logits
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, 64, n).tolist() for n in PROMPTS]
    for slot, (p, m) in enumerate(zip(prompts, NEW)):
        gen.admit_slot(slot, p, max_new=m)
    outs = [[] for _ in NEW]
    for _ in range(100):
        for slot, tok in gen.lane_step_ahead().items():
            outs[slot] += tok if isinstance(tok, list) else [tok]
        if [len(o) for o in outs] == list(NEW):
            break
    assert [len(o) for o in outs] == list(NEW) and not gen._in_flight
    logits = [[np.asarray(f.logits)[f.rows[slot][0]] for f in flights
               if slot in f.rows] for slot in range(len(NEW))]
    return prompts, outs, [np.stack(lg) for lg in logits]


def test_logits_served_a_step_ahead_equal_the_reference_full_forward():
    """The float32 logits behind every emitted token — chunked prefill
    (two chunks a step beside decoding lanes; prompts of 23 and 40 tokens
    turn the window layers' ring of 8-token windows over several times),
    then paged decode through the global and the window cache, every step
    launched before the one before it is fetched — equal the reference's
    full forward.  Tolerance: both sides compute in float32 on the CPU and
    differ by the order of their sums; rtol 1e-4 with atol 2e-5 is
    ``test_paged_lm.py``'s.  Attention runs through the Pallas kernel
    (interpreted); the gateway test below takes the XLA form."""
    cfg = tiny_cfg()
    gen = make_generator(cfg, attn_impl="pallas_interpret")
    assert set(gen.groups) == {"global", "window"}
    assert gen.layout["groups"]["window"]["spec"].layers == (0, 1, 2)
    assert gen.layout["groups"]["global"]["spec"].layers == (3,)
    prompts, outs, logits = serve_ahead(gen)
    for want, got in zip(reference_logits(cfg, prompts, outs), logits):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    counted = gen.counters()
    assert counted["prompt_tokens_prefilled"] == sum(PROMPTS)
    assert counted["prefill_chunks"] == sum(-(-n // 8) for n in PROMPTS)
    assert counted["steps_ahead"] > 0 and counted["moe_pairs_here"] > 0
    assert counted["window_pages_recycled"] > 0
    assert counted["kv_bytes_per_token"] == 1 * 2 * (2 * 16) * 4


# -- a program that leaves a part of the block out ---------------------------

def _no_gate(monkeypatch):
    monkeypatch.setattr(layers, "sigmoid_gate", lambda x, gate, **k: x)


def _no_qk_norm(monkeypatch):
    """Queries and keys go on as they left their projections (the scales
    stay declared, so the artifact still loads)."""
    real = layers.rms_norm

    def without(x, *a, scope=None, **k):
        y = real(x, *a, scope=scope, **k)
        return x if scope == "attn/qk_norm" else y

    monkeypatch.setattr(layers, "rms_norm", without)


def _no_post_norm(monkeypatch):
    """The attention sub-block's output is added as it left ``out``."""
    real = layers.rms_norm

    def without(x, param_attr=None, *a, **k):
        y = real(x, param_attr, *a, **k)
        return layers.cast(x, "float32") \
            if param_attr.name.endswith("attn_post_norm.w") else y

    monkeypatch.setattr(layers, "rms_norm", without)


def _no_embedding_scale(monkeypatch):
    monkeypatch.setattr(layers, "scale", lambda x, scale=1.0, **k: x)


def _rotary_in_global_layers(monkeypatch):
    """Every layer rotates its queries and keys, the global ones too."""
    real_norm, real_rotary = layers.rms_norm, layers.rotary_embedding

    def normed_and_rotated(x, *a, scope=None, **k):
        y = real_norm(x, *a, scope=scope, **k)
        if scope != "attn/qk_norm":
            return y
        pos = fluid.default_main_program().global_block().var("pos")
        return real_rotary(y, pos, x.shape[-1], 10000.0)

    monkeypatch.setattr(layers, "rms_norm", normed_and_rotated)
    monkeypatch.setattr(layers, "rotary_embedding", lambda x, *a, **k: x)


@pytest.mark.parametrize("fault", [
    _no_gate, _no_qk_norm, _no_post_norm, _no_embedding_scale,
    _rotary_in_global_layers],
    ids=["gate-dropped", "qk-norm-dropped", "post-norm-dropped",
         "embedding-scale-dropped", "global-layers-rotated"])
def test_a_program_that_leaves_part_of_the_block_out_does_not_agree(
        fault, monkeypatch):
    """Each is the whole difference between this block and one the engine
    served before; each moves the logits far past the tolerance (0.05 and
    more against 2e-5), and the reference's own best token is no longer
    the served one at some position."""
    fault(monkeypatch)
    cfg = tiny_cfg()
    prompts, outs, logits = serve_ahead(make_generator(cfg, prefill_slots=1))
    want = reference_logits(cfg, prompts, outs)
    worst = max(np.abs(g - w).max() for g, w in zip(logits, want))
    assert worst > 0.05, worst


def test_the_whole_model_through_the_gateway_follows_the_reference(tmp_path):
    """Registry artifact (float32 masters) -> Gateway.load_model (the
    builder found by the published ``model_type``) -> scheduler ->
    ``lane_step_ahead``: greedy tokens equal the reference's argmax,
    teacher-forced through its full forward (no cache, no paging)."""
    cfg = tiny_cfg()
    root = str(tmp_path / "models")
    serve_cell.write_artifact(cfg, SEED, root)
    gw = Gateway(registry=ModelRegistry(root=root), n_slots=cfg["n_slots"],
                 max_new_tokens=cfg["max_out_len"])
    key = gw.load_model(cfg["param_prefix"], serve_cell.VERSION)
    inst = gw.registry.instance(key)
    assert isinstance(inst, PagedLMGenerator)
    assert inst.builder.__name__ == "paddle_tpu.models.afmoe"
    assert gw.registry.entries()[0]["kind"] == "lm_generator"
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, 64, n).tolist() for n in (3, 8, 17, 40, 9, 31)]
    new = [5, 16, 9, 12, 16, 7]
    warmed = gw.sched.stats()["engine"]["prompt_tokens_prefilled"]
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
    assert stats["prompt_tokens_prefilled"] - warmed \
        == sum(map(len, prompts))
    assert stats["steps_ahead"] > 0
    assert stats["global_pages_in_use"] == stats["window_pages_in_use"] == 0


def test_what_the_builder_reads_and_what_it_refuses():
    from paddle_tpu.models import afmoe as M

    cfg = tiny_cfg()
    c = M.config_from_dict(cfg)
    assert c.layer_kinds == ("window", "window", "window", "global")
    assert c.layer_moe == (False, False, True, True)
    assert (c.num_experts, c.experts_held, c.first_expert) == (32, 8, 0)
    assert (c.route_scale, c.embedding_scale) == (2.826, 32 ** 0.5)
    assert M.param_shapes(c, "trinity") == \
        {k: tuple(v) for k, v in fam.param_shapes(cfg, "trinity").items()}
    specs = M.cache_specs(c)
    assert specs["window"].window == 8 and specs["global"].window is None
    assert not specs["window"].latent and specs["global"].kv_heads == 2
    for key, value in (("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("n_group", 4), ("score_func", "softmax"),
                       ("route_norm", False)):
        with pytest.raises(NotImplementedError, match=key):
            M.config_from_dict({**cfg, key: value})


# -- the ops this block added ------------------------------------------------

def test_qk_norm_and_the_gate_match_the_reference():
    """``rms_norm`` on [T, H, D] with a [D] scale normalises every head by
    itself; ``sigmoid_gate`` is x * sigmoid(gate) in float32."""
    x = RNG.normal(size=(7, 4, 16)).astype(np.float32) * 3
    g = (1 + 0.1 * RNG.normal(size=16)).astype(np.float32)
    got = run_op("rms_norm", {"X": x, "Scale": g},
                 {"epsilon": 1e-5, "scope": "attn/qk_norm"})["Out"]
    np.testing.assert_allclose(got, ref.rms_norm(x, g, 1e-5), rtol=1e-6,
                               atol=1e-6)
    # every head of every token comes out at unit mean square
    np.testing.assert_allclose(((got / g) ** 2).mean(-1), np.ones((7, 4)),
                               rtol=1e-4)
    a = RNG.normal(size=(7, 64)).astype(np.float32)
    gate = RNG.normal(size=(7, 64)).astype(np.float32)
    out = run_op("sigmoid_gate", {"X": a, "Gate": gate},
                 {"scope": "attn/gate"})["Out"]
    np.testing.assert_allclose(out, a / (1 + np.exp(-gate)), rtol=1e-6,
                               atol=1e-6)
    low = run_op("sigmoid_gate", {"X": jnp.asarray(a, jnp.bfloat16),
                                  "Gate": jnp.asarray(gate, jnp.bfloat16)})
    assert low["Out"].dtype == jnp.bfloat16


# -- the share and the model -------------------------------------------------

def test_the_8_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Eight chips share the layer, 4 of 32 experts each (``first_expert``
    0, 4 .. 28): what each computes for the tokens routed to it, and the
    shared expert counted ONCE, sum — BEFORE ``ffn_post_norm``, where the
    deployment's exchange sits — to the ``y`` the uncut reference gives
    for the whole layer; and each share is the reference's share."""
    n, d, f, top_k, scale, t = 32, 32, 16, 4, 2.826, 24
    w = {"router.w": RNG.normal(size=(d, n)) * d ** -0.5,
         "router.bias": 0.02 * RNG.normal(size=n),
         "experts.gate.w": RNG.normal(size=(n, d, f)) * d ** -0.5,
         "experts.up.w": RNG.normal(size=(n, d, f)) * d ** -0.5,
         "experts.down.w": RNG.normal(size=(n, f, d)) * f ** -0.5,
         "shared.gate.w": RNG.normal(size=(d, f)) * d ** -0.5,
         "shared.up.w": RNG.normal(size=(d, f)) * d ** -0.5,
         "shared.down.w": RNG.normal(size=(f, d)) * f ** -0.5}
    w = {k: v.astype(np.float32) for k, v in w.items()}
    x = RNG.normal(size=(t, d)).astype(np.float32)

    def share(first, held, **scaled):
        return run_op("routed_experts", {
            "X": x, "RouterW": w["router.w"], "RouterBias": w["router.bias"],
            "WGate": w["experts.gate.w"][first:first + held],
            "WUp": w["experts.up.w"][first:first + held],
            "WDown": w["experts.down.w"][first:first + held]},
            {"top_k": top_k, "first_expert": first, "impl": "xla", **scaled})

    def reference(first, held, shared):
        z = ref.sizes(tiny_cfg(num_experts=held, first_expert=first))
        W = {f"p.moe.{k}": jnp.asarray(
            v[first:first + held] if k.startswith("experts.") else v)
            for k, v in w.items()}
        with jax.default_matmul_precision("highest"):
            out, _ = ref.moe(F32, W, "p", jnp.asarray(x), z, shared=shared)
        return np.asarray(out)

    parts = [share(first, 4, routed_scale=scale)
             for first in range(0, n, 4)]
    shared = run_op("gated_ffn", {
        "X": x, "WGate": w["shared.gate.w"], "WUp": w["shared.up.w"],
        "WDown": w["shared.down.w"]}, {"scope": "ffn/shared"})["Out"]
    np.testing.assert_allclose(
        sum(p["Out"] for p in parts) + shared, reference(0, n, True),
        rtol=2e-4, atol=3e-5)
    assert sum(int(p["Load"].sum()) for p in parts) == t * top_k
    np.testing.assert_allclose(parts[5]["Out"], reference(20, 4, False),
                               rtol=2e-4, atol=3e-5)
    # the scale is no rounding: a share without it is 2.826 times smaller
    np.testing.assert_allclose(scale * share(0, 4)["Out"], parts[0]["Out"],
                               rtol=1e-5, atol=1e-6)
