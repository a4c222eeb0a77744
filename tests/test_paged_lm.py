"""The decoder-only paged generator (ISSUE 28) at a small size on the CPU:
the whole model, prefill then paged decode, through ``ModelRegistry`` ->
``Gateway`` -> the scheduler -> ``PagedLMGenerator.lane_step`` against the
plain reference's full forward on seeded weights; window pages recycling
under a long generation; the two page groups under admission pressure; and
what the generator refuses."""

import json

import numpy as np
import pytest

from paddle_tpu.serving import (ContinuousBatchingScheduler, PageGroup,
                                PagedLMGenerator, PoolCapacityError)
from paddle_tpu.serving.gateway import Gateway, ModelRegistry
from perfbench import serve_cell, weights
from perfbench.families import mimo_v2_flash as fam

SEED = 2800000028


def tiny_cfg(**over):
    with open("perfbench/configs/mimo-v2-flash-ep32.json",
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
    logits, _ = fam.ref.forward_logits(
        lambda shapes: weights.make(shapes, seed, kind_of=fam.leaf_kind),
        cfg["param_prefix"], cfg, seqs, [len(o) for o in outputs])
    return [np.asarray(x) for x in logits]


def test_the_whole_model_through_the_gateway_follows_the_reference(tmp_path):
    """Registry artifact (float32 masters) -> Gateway.load_model ->
    scheduler -> lane_step: greedy tokens equal the reference's argmax,
    teacher-forced through its full forward (no cache, no paging), for
    prompts shorter and longer than a chunk, a page and the window."""
    cfg = tiny_cfg()
    root = str(tmp_path / "models")
    serve_cell.write_artifact(cfg, SEED, root)
    gw = Gateway(registry=ModelRegistry(root=root), n_slots=cfg["n_slots"],
                 max_new_tokens=cfg["max_out_len"])
    key = gw.load_model(cfg["param_prefix"], serve_cell.VERSION)
    inst = gw.registry.instance(key)
    assert isinstance(inst, PagedLMGenerator)
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
        best = lg.max(axis=-1)
        gap = best - lg[np.arange(len(out)), out]
        assert gap.max() < 1e-4, gap.max()
    stats = gw.sched.stats()["engine"]
    assert stats["moe_pairs_here"] > 0 and stats["window_pages_recycled"] > 0
    # every page of both groups came back
    assert stats["global_pages_in_use"] == stats["window_pages_in_use"] == 0


@pytest.mark.parametrize("vmem, tile", [(None, 8), (50_000, 4)])
def test_step_logits_equal_the_reference_full_forward(monkeypatch, vmem,
                                                      tile):
    """Not only the argmax: the float32 logits behind every emitted token,
    prefill (two chunks a step beside decoding lanes) then paged decode.
    A chunk's queries go through attention in tiles as large as the
    kernel's fast memory takes: whole at this size, and in two tiles under
    a smaller budget."""
    import importlib

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

    if vmem is not None:
        monkeypatch.setattr(fa, "SPLIT_VMEM_BYTES", vmem)
    cfg = tiny_cfg()
    gen = make_generator(cfg)
    assert gen.tile == tile and cfg["chunk_size"] == 8
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


def test_every_split_calls_walk_is_noted_once_a_compile(monkeypatch):
    """ISSUE 44: ``lowering/attn_split``, an instant under the compile of
    a step, says of each paged-attention call which kernel it is, its
    queries a lane, its table's slots, how many of them a grid step takes
    and the grid's steps: several slots for decode rows, one for the
    tiles of a chunk where fast memory holds half a chunk at a time;
    nothing is written by a step that is not compiled."""
    import importlib

    from paddle_tpu.observability import tracer

    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    monkeypatch.setattr(fa, "SPLIT_VMEM_BYTES", 50_000)
    before = len(tracer().events(name="lowering/attn_split"))
    cfg = tiny_cfg()
    gen = make_generator(cfg, attn_impl="pallas_interpret")
    assert gen.tile == 4
    lanes = gen.layout["lanes"]
    tables = {f"paged_attn_{kind}": g["table"]
              for kind, g in gen.layout["groups"].items()}
    gen.admit_slot(0, list(range(2, 25)), max_new=8)
    gen.admit_slot(1, list(range(2, 9)), max_new=8)
    for _ in range(3):
        gen.lane_step()
    notes = [e["args"] for e in
             tracer().events(name="lowering/attn_split")[before:]]
    assert {n["kernel"] for n in notes} == set(tables)
    for n in notes:
        assert n["slots"] == tables[n["kernel"]]
        k = n["slots_per_step"]
        assert k == (4 if n["queries"] == 1 else 1)
        kernel_lanes = lanes if n["queries"] == 1 \
            else n["grid_steps"] // n["slots"]
        assert kernel_lanes in (lanes, 2, 4)          # 1 or 2 chunks' tiles
        assert n["grid_steps"] == kernel_lanes * -(-n["slots"] // k)
    assert {n["queries"] for n in notes} == {1, 4}
    gen.lane_step()                        # decode rows alone: compiled
    seen = len(tracer().events(name="lowering/attn_split"))
    gen.lane_step()                        # the same program: no note
    assert len(tracer().events(name="lowering/attn_split")) == seen


def test_a_thousand_token_generation_holds_a_bounded_ring_of_window_pages():
    """Window pages are handed out as a ring and given back behind
    position t - window: a 1000-token generation never holds more than the
    ring, recycles a page every ``window_page_size`` tokens, and its
    logits still equal the reference's."""
    cfg = tiny_cfg(src_len=40, max_out_len=1000)
    gen = make_generator(cfg)
    layout = gen.layout["groups"]
    prompt = np.random.default_rng(3).integers(2, 64, 21).tolist()
    gen.admit_slot(0, prompt, max_new=1000)
    out, logits, held = [], [], []
    while len(out) < 1000:
        emitted, lg = gen.step_logits()
        held.append(gen.groups["window"].in_use())
        if 0 in emitted:
            out.append(emitted[0])
            logits.append(lg[0])
    assert max(held) <= layout["window"]["table"]
    decoding = held[len(prompt) // cfg["chunk_size"] + 1:]
    assert max(decoding) <= layout["window"]["decode_pages"]
    recycled = gen.groups["window"].stats()["recycled"]
    assert recycled >= 1000 // layout["window"]["page_size"] - 2
    # the global group kept every position
    assert gen.groups["global"].in_use() == \
        -(-(len(prompt) + 999) // layout["global"]["page_size"])
    want = reference_logits(cfg, [prompt], [out])[0]
    np.testing.assert_allclose(np.stack(logits), want, rtol=2e-4, atol=5e-5)
    # only the request's own tokens were routed: the three idle lanes and
    # the chunks' padding made no pair
    counted = gen.counters()
    moe_layers = sum(cfg["moe_layer_freq"][:cfg["num_hidden_layers"]])
    assert 0 < counted["moe_pairs_here"] <= \
        (len(prompt) + 999) * cfg["num_experts_per_tok"] * moe_layers
    assert np.sum(counted["expert_load"]) == counted["moe_pairs_here"]
    gen.clear_slot(0)
    assert gen.groups["window"].in_use() == gen.groups["global"].in_use() == 0


def test_page_groups_under_admission_pressure():
    """Admission asks BOTH groups: a pool with room in one and none in the
    other admits nothing; retiring a request lets the next in; a request no
    pool could ever hold is infeasible, not queued."""
    cfg = tiny_cfg()
    ring = 5                                    # (8 + 8 - 2) / 4 up, + 1
    # 12 window pages: two prefilling lanes' rings and 2 over; 20 global
    # pages: two long requests' worth and 6 over
    gen = make_generator(cfg, window_pages=13, num_pages=21)
    assert gen.layout["groups"]["window"]["table"] == ring
    long_prompt, short = list(range(2, 42)), [5, 6, 7]
    assert gen.pages_needed(long_prompt, 16) == {"global": 7, "window": ring}
    gen.admit_slot(0, long_prompt, max_new=16)
    gen.admit_slot(1, long_prompt, max_new=16)
    # window group exhausted by two prefilling rings: no third lane yet,
    # though the global group has pages left
    assert gen.groups["global"].can_reserve(1)
    assert not gen.can_admit(short, 4)
    with pytest.raises(PoolCapacityError):
        gen.admit_slot(2, short, max_new=4)
    # once a lane decodes, its ring shrinks to the decode need and the
    # pages come back to admission
    for _ in range(8):
        gen.lane_step()
    assert gen._lanes[0].phase == "decode"
    assert gen.can_admit(short, 4)
    gen.admit_slot(2, short, max_new=4)
    gen.clear_slot(2)
    # the global group refuses what the window group would take
    assert gen.groups["window"].can_reserve(ring)
    assert not gen.can_admit(long_prompt, 16)
    gen.clear_slot(0)
    assert gen.can_admit(long_prompt, 16)
    # never admissible: more pages than a whole group holds
    tiny = make_generator(cfg, num_pages=4)
    assert tiny.prompt_infeasible(long_prompt, 16)
    assert not tiny.prompt_infeasible(short, 4)


def test_the_scheduler_queues_behind_a_full_group_and_fails_nothing():
    cfg = tiny_cfg()
    gen = make_generator(cfg, window_pages=2 * 5 + 1)
    sched = ContinuousBatchingScheduler(gen, n_slots=cfg["n_slots"],
                                        max_new_tokens=16)
    rng = np.random.default_rng(4)
    reqs = [sched.submit(rng.integers(2, 64, n).tolist(), 6)
            for n in (30, 35, 40, 12, 3, 25, 38, 9)]
    sched.run_until_idle(max_steps=400)
    assert all(r.done and r.error is None and len(r.tokens) == 6
               for r in reqs)
    stats = sched.stats()
    assert stats["failed"] == 0 and stats["peak_in_flight"] >= 2
    with pytest.raises(ValueError, match="prompt cap"):
        sched.submit(list(range(2, 60)), 4)


def test_residency_is_what_the_step_program_declares(tmp_path):
    """A model resident in bfloat16 keeps its matrices so, and norm
    scales, sinks and the ROUTER (matrix and selection bias: the published
    gate scores in float32) in float32; the registry's loader casts each
    tensor of the float32 artifact to what the program declares.  The
    model is a builder the generator is given, or finds by
    ``model_type``."""
    import types

    from paddle_tpu.models import decoder_lm, mimo_v2_flash
    from paddle_tpu.serving.paged_lm import LM_CONFIG_KEYS

    cfg = tiny_cfg(dtype="bfloat16", kv_dtype="bfloat16")
    conf = fam.serving(cfg)["manifest"]["config"]
    assert set(conf) <= set(LM_CONFIG_KEYS) and "attn_tile" not in conf
    assert decoder_lm(conf["model"]["model_type"]) is mimo_v2_flash
    root = str(tmp_path / "models")
    serve_cell.write_artifact(cfg, SEED, root)
    registry = ModelRegistry(root=root)
    gen = registry.instance(registry.load(cfg["param_prefix"],
                                          serve_cell.VERSION))
    kept = gen.param_dtypes()
    assert set(kept) == set(fam.param_shapes(cfg, cfg["param_prefix"]))
    for name, dtype in kept.items():
        want = "float32" if name.endswith(
            ("_norm.w", ".sink", "router.w", "router.bias")) else "bfloat16"
        assert dtype == want, name
        assert str(gen.scope.find_var(name).dtype) == want, name
    # a builder handed in: no model_type asked, no model looked up
    nameless = {k: v for k, v in conf["model"].items() if k != "model_type"}
    with pytest.raises(ValueError, match="model_type"):
        PagedLMGenerator(**dict(conf, model=nameless))
    with pytest.raises(KeyError, match="no decoder-only model"):
        PagedLMGenerator(**dict(conf, model=dict(nameless, model_type="x")))
    builder = types.SimpleNamespace(
        config_from_dict=mimo_v2_flash.config_from_dict,
        cache_specs=mimo_v2_flash.cache_specs,
        param_shapes=mimo_v2_flash.param_shapes,
        build_serve_step=mimo_v2_flash.build_serve_step)
    given = PagedLMGenerator(**dict(conf, model=nameless), builder=builder)
    assert given.param_dtypes() == kept


def _both_engines(which):
    """A tiny generator of the model with a window ring (``mimo``) or of
    the one with a latent pool (``moonlight``), and a maker of another
    with pools of a given size."""
    from perfbench.families import deepseek_v3

    family, file = {"window-ring": (fam, "mimo-v2-flash-ep32"),
                    "latent-pool": (deepseek_v3, "moonlight-16b-a3b-l5")
                    }[which]
    with open(f"perfbench/configs/{file}.json", encoding="utf-8") as f:
        cfg = {**json.load(f), **family.REHEARSAL["serve"]["cfg"]}
    conf = family.serving(cfg)["manifest"]["config"]
    shapes = family.param_shapes(cfg, cfg["param_prefix"])

    def make(**over):
        gen = PagedLMGenerator(**dict(conf, **over))
        gen.load_weights(weights.make(shapes, SEED,
                                      kind_of=family.leaf_kind))
        gen.open_slots(conf["lanes"])
        return gen

    return make


# (name, slot, prompt length, max_new): A, B, C enter at once; D takes A's
# slot and E takes C's in the very call after those let go
_SCRIPT = (("A", 0, 5, 6), ("B", 1, 19, 12), ("C", 2, 9, 10),
           ("D", 0, 5, 6), ("E", 2, 9, 10))


def _drive(gen, step, limit=200):
    """What a scheduler does with the script: A and D and E run to their
    caps; B's fourth token is its end of sequence and C is cancelled once
    3 of its tokens were seen (to the engine the same: a lane cleared
    short of its cap).  A slot that lets go is admitted again before the
    next call.  Returns each request's tokens as the caller saw them, the
    calls made, and what was left unreserved with A, B and C in."""
    rng = np.random.default_rng(41)
    prompts = {name: rng.integers(2, 64, n).tolist()
               for name, _, n, _ in _SCRIPT}
    spec = {name: (slot, cap) for name, slot, _, cap in _SCRIPT}
    seen = {name: [] for name in spec}
    holder, waiting = {}, {"A": "D", "C": "E"}

    def admit(name):
        slot, cap = spec[name]
        gen.admit_slot(slot, prompts[name], max_new=cap)
        holder[slot] = name

    def let_go(slot):
        name = holder.pop(slot)
        gen.clear_slot(slot)
        if name in waiting:
            admit(waiting[name])

    for name in "ABC":
        admit(name)
    reserved = {k: g.unreserved() for k, g in gen.groups.items()}
    calls = 0
    while holder and calls < limit:
        calls += 1
        for slot, toks in step().items():
            name = holder.get(slot)
            for tok in toks if isinstance(toks, list) else [toks]:
                if holder.get(slot) != name or name is None:
                    break       # past its end: what a scheduler drops
                seen[name].append(tok)
                if len(seen[name]) == {"B": 4, "C": 3}.get(
                        name, spec[name][1]):
                    let_go(slot)
        for slot, name in holder.items():
            # never a position past what admission reserved
            lane = gen._lanes[slot]
            assert lane.pos <= len(prompts[name]) + spec[name][1] - 1
    assert not holder, f"still running after {calls} calls: {holder}"
    return seen, calls, reserved


@pytest.mark.parametrize("which", ["window-ring", "latent-pool"])
def test_a_step_ahead_gives_token_for_token_what_lane_step_gives(which):
    """``lane_step_ahead`` against ``lane_step`` over one script: a prompt
    whose prefill ends in the step in flight (every one), a request told
    its end of sequence mid-stream, requests ending at their caps, a
    cancel with a step in flight, slots admitted again in the very next
    call; pools reserved to the last page.  Each request receives the
    tokens it received before, none of the request that held its slot; the
    counters read what the script implies."""
    make = _both_engines(which)
    probe = make()
    need = {name: probe.pages_needed(np.zeros(n), cap)
            for name, _, n, cap in _SCRIPT}
    pools = {kind: 1 + sum(need[name][kind] for name in "ABC")
             for kind in probe.groups}
    sized = dict(num_pages=pools["global"])
    if "window" in pools:
        sized["window_pages"] = pools["window"]
    gen = make(**sized)
    plain, calls, reserved = _drive(gen, gen.lane_step)
    assert set(reserved.values()) == {0}, reserved
    assert [len(plain[n]) for n in "ABCDE"] == [6, 4, 3, 6, 10]
    counted = gen.counters()
    assert counted["steps"] == calls
    assert counted["steps_ahead"] == counted["tokens_fed_on_device"] \
        == counted["stray_tokens_dropped"] == 0

    gen = make(**sized)
    ahead, calls_ahead, reserved = _drive(gen, gen.lane_step_ahead)
    assert ahead == plain
    assert set(reserved.values()) == {0}, reserved
    counted = gen.counters()
    # two rows were launched for a lane that was gone when they came
    # back: B's after its end of sequence, C's after its cancel; the
    # requests that ran to their caps cost none
    assert counted["stray_tokens_dropped"] == 2
    # every step but the first was launched with one in flight, and every
    # decode row took its input there: the 29 tokens seen and the 2
    # strays, less the 5 that came out of a prompt's last chunk
    assert counted["steps_ahead"] == counted["steps"] - 1
    assert counted["tokens_fed_on_device"] == 29 + 2 - 5
    assert calls_ahead <= calls
    assert not gen._in_flight
    for group in gen.groups.values():
        assert group.in_use() == 0 and group.stats()["holders"] == 0


def test_the_two_calls_after_each_other_and_what_is_refused():
    """A lane whose token the host has (a ``lane_step`` before) is fed
    from the host by the next ``lane_step_ahead``; a step in flight is
    never fetched out of turn; ``open_slots`` drops it."""
    gen = make_generator(tiny_cfg())
    prompt = [5, 9, 11]
    gen.admit_slot(0, prompt, max_new=8)
    first = gen.lane_step()[0]
    got = [first] + [gen.lane_step_ahead().get(0) for _ in range(3)]
    assert gen.counters()["tokens_fed_on_device"] == 3    # all but one row
    assert len(gen._in_flight) == 1
    for call in (gen.lane_step, gen.step_logits):
        with pytest.raises(RuntimeError, match="in flight"):
            call()
    gen.open_slots(4)
    assert not gen._in_flight
    gen.admit_slot(0, prompt, max_new=8)
    assert [gen.lane_step()[0] for _ in range(4)] == got


def test_page_group_accounting():
    g = PageGroup("window", 6, 4)
    g.reserve("a", 3)
    pages = [g.take("a") for _ in range(3)]
    assert 0 not in pages and g.in_use() == 3 and g.unreserved() == 2
    with pytest.raises(PoolCapacityError):
        g.take("a")                             # past its reservation
    with pytest.raises(PoolCapacityError):
        g.reserve("b", 3)                       # only 2 unreserved
    g.give("a", pages.pop())                    # recycled: "a" may take again
    assert g.stats()["recycled"] == 1 and g.in_use() == 2
    pages.append(g.take("a"))
    g.give("a", pages.pop())
    g.shrink("a", 2)
    g.reserve("b", 3)
    g.release("a", pages)
    assert g.in_use() == 0 and g.stats()["holders"] == 1


def test_what_the_generator_refuses_it_refuses_with_an_error():
    cfg = tiny_cfg()
    conf = fam.serving(cfg)["manifest"]["config"]
    for over, what in (({"prefix_sharing": True}, "prefix sharing"),
                       ({"kv_dtype": "int8"}, "int8"),
                       ({"mesh_axes": {"model": 2}}, "mesh_axes")):
        with pytest.raises(NotImplementedError, match=what):
            PagedLMGenerator(**dict(conf, **over))
    gen = make_generator(cfg)
    with pytest.raises(NotImplementedError, match="beam search"):
        gen.beam([[2, 3]], [2], 4)
    with pytest.raises(NotImplementedError, match="session"):
        gen.resume_slot(0, "s1")
    with pytest.raises(ValueError, match="outside the vocabulary"):
        gen.admit_slot(0, [2, 64], max_new=2)
    with pytest.raises(ValueError, match="built for 4 lanes"):
        gen.open_slots(8)
    # a session id on a request is refused, not dropped silently
    sched = ContinuousBatchingScheduler(gen, n_slots=4, max_new_tokens=4)
    req = sched.submit([2, 3, 4], 2, session="s1")
    sched.run_until_idle(max_steps=20)
    assert isinstance(req.error, NotImplementedError)
    # and a speculative pair cannot be made of it
    registry = ModelRegistry()
    registry.register("mimo", "1", gen)
    assert registry.entries()[0]["kind"] == "lm_generator"
    assert registry.entries()[0]["hbm_bytes"] > 0
