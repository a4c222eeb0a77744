"""The one road from an executable-cache miss to a running step (ISSUE 30).

Covers: every executable the executor builds donates its state, read off
the executable ``Executor._jit_step`` resolved, for each kind of dispatch
the system has; what the in-memory executable cache keys on; a second
process finding every executable in JAX's compilation cache; an older
publish's ``compiled/`` directory and a ``PADDLE_TPU_AOT_CACHE`` in the
environment being ignored; the closed bucket set of the unified program;
and the per-program rng-salt regression (the PR 12 note's cross-module
test-order sensitivity)."""

import contextlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.parallel import mesh as pmesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- every executable aliases the state it is given ---------------------------

@contextlib.contextmanager
def resolving():
    """Spy on ``Executor._jit_step``: yields the list that receives, for
    each executable the executor resolves inside the block, what its first
    dispatch lowers to."""
    seen = []
    real = fluid.Executor._jit_step

    def spy(step, in_shardings=None):
        jitted = real(step, in_shardings)
        first = []

        def call(*args):
            if not first:
                first.append(jitted.lower(*args))
                seen.append(first[0])
            return jitted(*args)

        call.trace = jitted.trace       # the executor traces inside its span
        return call

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fluid.Executor, "_jit_step", staticmethod(spy))
        yield seen


_STATE_ARG = re.compile(r"%arg(\d+): [^%]*?loc\(\"state\['([^']+)'\]")


def state_aliasing(lowered):
    """(state variables the executable takes, those of them the COMPILED
    module aliases to an output).  Names come from the lowering's argument
    locations, aliases from ``input_output_alias`` in the compiled
    module's header, as ``test_tpu_compile.py`` reads it for the chip."""
    text = lowered.as_text(debug_info=True)
    sig = text[text.index("func.func public @main("):].split("\n", 1)[0]
    names = {int(i): n for i, n in _STATE_ARG.findall(sig)}
    head = lowered.compile().as_text().splitlines()[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    params = {int(p) for p in re.findall(r"\}: \((\d+),", m.group(1))} \
        if m else set()
    return set(names.values()), {n for i, n in names.items() if i in params}


def assert_in_place(lowered, written):
    """The step returns every state entry, so every state buffer it is
    given finds an output to alias: none is copied, whether the step
    wrote it or not.  ``written`` names what this dispatch must update."""
    state, aliased = state_aliasing(lowered)
    assert state == aliased, f"copied, not aliased: {sorted(state - aliased)}"
    assert written(state), sorted(state)


def _tiny_generator(prefix):
    from paddle_tpu.serving import PagedTransformerGenerator

    return PagedTransformerGenerator(
        30, 30, n_layer=1, n_head=2, d_key=4, d_value=4, d_model=8,
        d_inner_hid=16, max_length=32, src_len=8, max_out_len=4,
        page_size=4, chunk_size=4, num_pages=32, param_prefix=prefix,
        place=fluid.CPUPlace())


def _adam_mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[6], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.fc(input=x, size=8, act="relu")
        loss = layers.mean(layers.square_error_cost(
            input=layers.fc(input=h, size=1), label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    feed = {"x": np.ones((4, 6), np.float32),
            "y": np.ones((4, 1), np.float32)}
    return main, startup, loss, feed


def _has_adam_state(state):
    return any("moment" in n for n in state) \
        and any(n.endswith(".w_0") for n in state)


def _train(how):
    main, startup, loss, feed = _adam_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with resolving() as seen:
            if how == "run":
                exe.run(main, feed=feed, fetch_list=[loss])
            elif how == "run_steps":
                exe.run_steps(main, feeds=[feed, feed, feed],
                              fetch_list=[loss])
            elif how == "run_pipeline":
                exe.run_pipeline(main, loader=[feed, feed],
                                 fetch_list=[loss])
            elif how == "run-mesh-dp2":
                with pmesh.mesh_guard(pmesh.make_mesh({"dp": 2})):
                    exe.run(main, feed=feed, fetch_list=[loss])
    return seen, _has_adam_state


def _pool_write(op):
    """``run`` in infer mode over one pool-writing op."""
    main = fluid.Program()
    n_layer, page_size, width = 2, 4, 6
    # K and V rows in one pool, or one pool of a split pair
    shape = (4 * n_layer * (2 if op == "paged_cache_write" else 1),
             page_size, width)
    feed = {"pages": np.array([[1], [3]], np.int32),
            "offs": np.array([[2], [0]], np.int32)}
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        pool = main.global_block().create_var(
            name="pool", shape=list(shape), dtype="float32",
            persistable=True)
        pages = layers.data("pages", [1], "int32")
        offs = layers.data("offs", [1], "int32")
        if op == "paged_cache_write":
            k = layers.data("k", [1, 2, 3], "float32")
            v = layers.data("v", [1, 2, 3], "float32")
            layers.paged_cache_write(pool, k, v, pages, offs, layer=1,
                                     n_layer=n_layer)
            feed["k"] = feed["v"] = np.ones((2, 1, 2, 3), np.float32)
        else:
            value = layers.data("value", [width], "float32")
            layers.paged_row_write(pool, value, layers.reshape(pages, [-1]),
                                   layers.reshape(offs, [-1]), layer=1,
                                   n_layer=n_layer)
            feed["value"] = np.ones((2, width), np.float32)
    scope = fluid.Scope()
    scope.set_var("pool", np.zeros(shape, np.float32))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope), resolving() as seen:
        exe.run(main, feed=feed, fetch_list=["pool"], mode="infer")
    assert np.count_nonzero(np.asarray(scope.find_var("pool"))) > 0
    return seen, lambda state: state == {"pool"}


@pytest.fixture(scope="module")
def paged_steps():
    """``PagedTransformerGenerator.lane_step`` at each tower width."""
    gen = _tiny_generator("tfa")
    gen.init_params(seed=7)
    with resolving() as seen:
        gen.aot_warm(4)
    assert gen.step_variants() == [1, 4]
    return dict(zip(gen.step_variants(), seen, strict=True))


@pytest.fixture(scope="module")
def lm_steps():
    """``PagedLMGenerator``'s step at each number of prefill chunks."""
    from paddle_tpu.serving import PagedLMGenerator
    from perfbench import weights
    from perfbench.families import mimo_v2_flash as fam

    with open(os.path.join(REPO, "perfbench/configs/mimo-v2-flash-ep32.json"),
              encoding="utf-8") as f:
        cfg = {**json.load(f), **fam.REHEARSAL["serve"]["cfg"]}
    conf = fam.serving(cfg)["manifest"]["config"]
    gen = PagedLMGenerator(**conf)
    gen.load_weights(weights.make(
        fam.param_shapes(cfg, cfg["param_prefix"]), 30, kind_of=fam.leaf_kind))
    with resolving() as seen:
        gen.aot_warm(conf["lanes"])
    assert gen.step_variants() == [0, 1, 2]
    return dict(zip(gen.step_variants(), seen, strict=True))


@pytest.fixture(scope="module")
def spec_steps():
    """The speculative pair's draft and verify programs at each tower
    width, and its copy-on-write program."""
    from paddle_tpu.serving import (PagedTransformerGenerator,
                                    SpeculativeGenerator, copy_weights)

    kw = dict(n_layer=1, n_head=2, d_key=4, d_value=4, d_model=16,
              d_inner_hid=32, max_length=64, src_len=8, max_out_len=8,
              page_size=4, chunk_size=4, num_pages=32,
              place=fluid.CPUPlace())
    target = PagedTransformerGenerator(24, 24, param_prefix="sat", **kw)
    target.init_params(seed=1)
    draft = PagedTransformerGenerator(24, 24, param_prefix="sad", **kw)
    copy_weights(target.scope, draft.scope, prefix="sat", dst_prefix="sad")
    spec = SpeculativeGenerator(target, draft, k=2, draft_name="sad")
    with resolving() as seen:
        spec.aot_warm(2)
    names = ["draft@1", "verify@1", "draft@2", "verify@2", "cow"]
    return dict(zip(names, seen, strict=True))


def _has_pool(state):
    return any("kv_pool" in n for n in state)


@pytest.mark.parametrize("dispatch", [
    "run", "run_steps", "run_pipeline", "run-mesh-dp2",
    "paged_cache_write", "paged_row_write"])
def test_executor_dispatch_updates_its_state_in_place(dispatch):
    """Parameters, Adam moments and pools are donated on every road into
    the executor: one dispatch, a scan of steps, a pipelined loop, a
    two-device mesh, and an infer step over each pool-writing op."""
    seen, written = (_pool_write if dispatch.startswith("paged_")
                     else _train)(dispatch)
    assert len(seen) == 1, "one signature, one executable"
    assert_in_place(seen[0], written)


@pytest.mark.parametrize("width", [1, 4])
def test_lane_step_updates_the_pool_in_place(width, paged_steps):
    assert_in_place(paged_steps[width], _has_pool)


@pytest.mark.parametrize("n_prefill", [0, 1, 2])
def test_lm_step_updates_both_pool_pairs_in_place(n_prefill, lm_steps):
    assert_in_place(
        lm_steps[n_prefill],
        lambda state: sum("kv_pool" in n for n in state) == 4)


@pytest.mark.parametrize("program", ["draft@1", "verify@1", "draft@2",
                                     "verify@2", "cow"])
def test_speculative_pair_updates_its_pools_in_place(program, spec_steps):
    assert_in_place(spec_steps[program], _has_pool)


# -- what the in-memory executable cache keys on ------------------------------

@pytest.mark.parametrize("change", ["nothing", "mode", "fetch_list", "mesh"])
def test_executable_cache_key(change):
    """The same program and signature is one entry and one miss, also when
    the program is built anew (the key holds its fingerprint, not its
    identity); another mode, fetch list or mesh is another entry.  (Another
    lane count is another feed signature:
    ``test_executor.py::test_cache_stats_and_log_recompiles`` holds that.)"""
    main, startup, loss, feed = _adam_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        before = exe.cache_stats()["executable"]
        again, _, loss2, _ = _adam_mlp()
        assert again is not main
        kw = dict(feed=feed, fetch_list=[loss2])
        mesh = contextlib.nullcontext()
        if change == "mode":
            kw["mode"] = "infer"
        elif change == "fetch_list":
            kw["fetch_list"] = []
        elif change == "mesh":
            mesh = pmesh.mesh_guard(pmesh.make_mesh({"dp": 2}))
        with mesh:
            exe.run(again, **kw)
        after = exe.cache_stats()["executable"]
    new = 0 if change == "nothing" else 1
    assert after["misses"] - before["misses"] == new
    assert after["size"] - before["size"] == new
    assert after["hits"] - before["hits"] == 1 - new


# -- persistence across processes is JAX's cache and nothing else -------------

_WARM = """
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from paddle_tpu import fluid
from paddle_tpu.serving import PagedTransformerGenerator
gen = PagedTransformerGenerator(
    30, 30, n_layer=1, n_head=2, d_key=4, d_value=4, d_model=8,
    d_inner_hid=16, max_length=32, src_len=8, max_out_len=4,
    page_size=4, chunk_size=4, num_pages=32, param_prefix="tfw",
    place=fluid.CPUPlace())
gen.init_params(seed=7)
gen.aot_warm(4)
print("MISSES", gen.exe.cache_stats()["executable"]["misses"])
"""


def test_second_process_compiles_nothing(tmp_path):
    """A generator's ``aot_warm`` in two processes that share
    ``JAX_COMPILATION_CACHE_DIR``: the second resolves the same
    executables (its in-memory cache misses as often) and adds no entry to
    the directory, so every compile it asked for was found there."""
    cache = tmp_path / "jax_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_ENABLE_COMPILATION_CACHE="true")

    def warm():
        proc = subprocess.run([sys.executable, "-c", _WARM], cwd=REPO,
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-4000:]
        entries = {n for n in os.listdir(cache) if n.endswith("-cache")}
        return proc.stdout.split("MISSES")[1].strip(), entries

    misses, first = warm()
    assert int(misses) >= 2 and len(first) >= 2
    assert warm() == (misses, first)


def _publish(root):
    from paddle_tpu.serving.gateway import ModelRegistry

    gen = _tiny_generator("tfs")
    gen.init_params(seed=7)
    return gen, ModelRegistry.save_generator_artifact(gen, root, "m", "1")


def test_stale_compiled_directory_is_ignored(tmp_path):
    """A version directory an older publish left a ``compiled/`` in loads
    as if it had none: the same admission budget, the same tokens, the
    directory untouched."""
    from paddle_tpu.serving.gateway import ModelRegistry

    root = str(tmp_path)
    gen, art = _publish(root)
    with open(os.path.join(art, "gateway.json"), encoding="utf-8") as f:
        cfg = json.load(f)["config"]
    cost = ModelRegistry._estimate_cost("generator", art, cfg)
    stale = os.path.join(art, "compiled")
    os.makedirs(stale)
    with open(os.path.join(stale, "0" * 64 + ".aotx"), "wb") as f:
        f.write(b"PTAOT1\0not an executable")
    assert ModelRegistry._estimate_cost("generator", art, cfg) == cost
    reg = ModelRegistry(root=root, place=fluid.CPUPlace())
    inst = reg.instance(reg.load("m", "1"))
    assert reg.entries()[0]["hbm_bytes"] == cost
    src, lens = np.arange(2, 8).reshape(1, 6), np.array([6])
    assert np.array_equal(inst.greedy(src, lens, max_new=3),
                          gen.greedy(src, lens, max_new=3))
    assert os.listdir(stale) == ["0" * 64 + ".aotx"]


def test_aot_cache_variable_has_no_effect(tmp_path, monkeypatch):
    """``PADDLE_TPU_AOT_CACHE`` mounted a private cache once; now nothing
    reads it: the step still donates and the directory is never made."""
    monkeypatch.setenv("PADDLE_TPU_AOT_CACHE", str(tmp_path / "aot"))
    seen, written = _train("run")
    assert_in_place(seen[0], written)
    assert not os.path.exists(tmp_path / "aot")


# -- the closed bucket set ----------------------------------------------------

def test_generator_bucket_set_is_closed():
    from paddle_tpu.serving.paged_decoder import tower_widths

    gen = _tiny_generator("tfd")
    buckets = gen.bucket_set(n_slots=4)
    assert len(buckets) == len(tower_widths(4)) == 2 \
        and all(b["closed"] for b in buckets), \
        "the unified program must enumerate to exactly ONE signature " \
        "per width of its prefill tower"


# -- rng-salt order-independence (PR 12 note / ISSUE 14 satellite) ------------

def _seeded_generation():
    gen = _tiny_generator("tfo")
    gen.init_params(seed=7)
    toks = gen.greedy(np.arange(2, 8).reshape(1, 6), np.array([6]),
                      max_new=3)
    return toks, gen._unified[0].desc.fingerprint()


def test_generation_independent_of_prior_program_builds():
    """The PR 12 note's cross-module order sensitivity, distilled: a
    process-global rng-salt counter made an identically-seeded build
    depend on how many random ops ANY earlier program created —
    different salts -> different param init -> a generation truncated
    when an unlucky token landed on end_id.  Salts are per-program now:
    builds are order-independent AND fingerprint-stable (without which
    no executable cache could ever hit across builds)."""
    t1, fp1 = _seeded_generation()
    # simulate an unrelated suite building random-op-bearing programs
    for _ in range(3):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            h = fluid.layers.fc(input=x, size=16, act="relu")
            fluid.layers.dropout(h, dropout_prob=0.3)
    t2, fp2 = _seeded_generation()
    assert fp1 == fp2, "identical builds must share a fingerprint"
    assert np.array_equal(t1, t2), \
        "seeded generation depends on unrelated earlier program builds"


def test_appended_op_salt_never_collides_after_deserialize():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        h = fluid.layers.dropout(fluid.layers.fc(input=x, size=8),
                                 dropout_prob=0.5)
    clone = fluid.Program.parse_from_string(main.serialize_to_string())
    salts = [op.attrs["__rng_salt__"] for b in clone.desc.blocks
             for op in b.ops if "__rng_salt__" in op.attrs]
    with fluid.program_guard(clone):
        fluid.layers.dropout(clone.global_block().vars[h.name],
                             dropout_prob=0.5)
    new_salts = [op.attrs["__rng_salt__"] for b in clone.desc.blocks
                 for op in b.ops if "__rng_salt__" in op.attrs]
    assert len(set(new_salts)) == len(new_salts), \
        f"salt collision after deserialize: {salts} -> {new_salts}"


@pytest.mark.slow
def test_cross_module_suite_order(tmp_path):
    """Run the two suites of the PR 12 note in the offending order —
    test_observability BEFORE the paged gateway tests — in a
    subprocess.  Under the old process-global salt counter, the
    observability suite's program builds shifted the gateway
    generators' init streams and could truncate a generation to one
    token (the recorded "assert 1 == 3")."""
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:randomly",
         "-p", "no:cacheprovider", "-m", "not slow",
         "tests/test_observability.py", "tests/test_gateway.py"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, \
        f"suite order regressed:\n{proc.stdout[-4000:]}"
