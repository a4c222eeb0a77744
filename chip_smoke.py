"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at the full width of Transformer-base (6 layers, 8 heads x 64, d_model
512, d_inner 2048, vocab 32768; random weights from a seed), in ONE
process:

  train    bench recipe (fused flash attention, fused vocab loss, bf16
           amp, Adam) through ``fluid.Executor.run`` at batch 64 x s 256
           and batch 8 x s 2048 — loss finite and falling, no recompile
           after the first step, Mosaic kernels in the compiled step
  kernels  compiled ``ragged_decode_attention(impl="pallas")`` against
           ``impl="xla"`` at the serve shapes: float32 / bfloat16 / int8
           pools, C = 1 and C = chunk, causal and not, one dead lane
  serve    ``PagedTransformerGenerator`` -> ``save_generator_artifact``
           -> ``ModelRegistry`` -> ``Gateway.load_model`` ->
           ``GatewayServer``; concurrent HTTP ``/v1/generate`` requests
           (one streamed) whose tokens must equal the generator's own
           greedy decode; state donated, no private compile cache
  multichip  with >= 4 devices: the train step under dp=4 and
           dp=2 x mp=2, and the served model over batch=1 x model=4

Any failed check raises, so the exit code is non-zero and no result line
is printed.  Without a TPU the script refuses before building anything;
``--rehearse-cpu`` runs the same phases tiny, with the Pallas kernels in
interpret mode, and says ``platform: cpu`` — a rehearsal of the control
flow, never a measurement.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib
import importlib.metadata
import json
import os
import shutil
import sys
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chip_smoke_out")
SEED = 20

# Transformer-base as bench.py sizes it — the one model training and
# paged serving share.  Width is never cut; the rehearsal is a different,
# tiny model whose only job is to walk the same code.
FULL = {
    "model": dict(n_layer=6, n_head=8, d_key=64, d_value=64, d_model=512,
                  d_inner_hid=2048),
    "vocab": 32768,
    # (batch, seq_len, steps): s=256 is the bench shape; s=2048 is the
    # first shape past PALLAS_BWD_MIN_L, where dq/dkv are Pallas too
    "train": [(64, 256, 5), (8, 2048, 2)],
    "learning_rate": 1e-4,              # the bench recipe's
    "serve": dict(src_len=256, max_out_len=64, page_size=16, chunk_size=32,
                  n_slots=4, n_requests=10, min_prompt=32),
    # pallas vs xla on the chip: f32 matmuls run bf16 passes whose
    # rounding differs between Mosaic and XLA (my chip run, PR 21:
    # worst 5.4e-3 of max|ref| over all 36 geometries)
    "kernel_rel_tol": 2e-2,
}
REHEARSAL = {
    "model": dict(n_layer=1, n_head=4, d_key=8, d_value=8, d_model=16,
                  d_inner_hid=32),
    "vocab": 64,
    "train": [(8, 8, 5), (4, 16, 2)],
    # a model this small needs big steps to beat its dropout noise in 5
    "learning_rate": 1e-2,
    "serve": dict(src_len=16, max_out_len=6, page_size=4, chunk_size=8,
                  n_slots=4, n_requests=10, min_prompt=3),
    "kernel_rel_tol": 1e-4,
}
# mesh step vs one-chip step: bf16 reductions in another order and
# per-shard dropout masks
MESH_LOSS_REL_TOL = 2e-2
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def cache_entries() -> int:
    import jax

    path = jax.config.jax_compilation_cache_dir
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def build_train(cfg, seq_len: int, mp_shard: bool = False):
    """The bench_transformer recipe (bench.py) as programs."""
    from paddle_tpu import fluid
    from paddle_tpu.models import transformer as T

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        avg_cost, _, _ = T.transformer(
            src_vocab_size=cfg["vocab"], trg_vocab_size=cfg["vocab"],
            max_length=seq_len + 1, dropout_rate=0.1,
            src_seq_len=seq_len, trg_seq_len=seq_len, fused=True,
            materialize_attn_bias=False, fused_vocab_loss=True,
            amp_dtype="bfloat16", mp_shard=mp_shard, **cfg["model"])
        fluid.optimizer.Adam(
            learning_rate=cfg["learning_rate"]).minimize(avg_cost)
    main.random_seed = startup.random_seed = SEED
    return main, startup, avg_cost


def train_feed(cfg, batch: int, seq_len: int):
    rng = np.random.RandomState(SEED)
    vocab = cfg["vocab"]
    pos = np.tile(np.arange(seq_len, dtype=np.int32), (batch, 1))
    return {
        "src_word": rng.randint(1, vocab, (batch, seq_len)).astype(np.int32),
        "src_pos": pos,
        "trg_word": rng.randint(1, vocab, (batch, seq_len)).astype(np.int32),
        "trg_pos": pos,
        "lbl_word": rng.randint(1, vocab, (batch, seq_len)).astype(np.int32),
        "lbl_weight": np.ones((batch, seq_len), np.float32),
    }


def run_train_steps(exe, main, startup, avg_cost, feed, steps: int):
    """startup + ``steps`` steps in a fresh scope; -> (losses, scope,
    seconds to the first loss, seconds for the rest)."""
    from paddle_tpu import fluid

    scope = fluid.Scope()
    t0 = time.perf_counter()
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = [float(exe.run(main, feed=feed, fetch_list=[avg_cost])[0])]
        t1 = time.perf_counter()
        misses = exe.cache_stats()["executable"]["misses"]
        for _ in range(steps - 1):
            losses.append(
                float(exe.run(main, feed=feed, fetch_list=[avg_cost])[0]))
        t2 = time.perf_counter()
        check(exe.cache_stats()["executable"]["misses"] == misses,
              "train: a step after the first recompiled")
    check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    return losses, scope, t1 - t0, t2 - t1


def phase_train(cfg, on_chip: bool):
    """-> (losses at the first shape, for the mesh phase to match)."""
    from paddle_tpu import fluid

    n_attn = 3 * cfg["model"]["n_layer"]    # enc self, dec self, dec cross
    first = None
    for i, (batch, seq_len, steps) in enumerate(cfg["train"]):
        main, startup, avg_cost = build_train(cfg, seq_len)
        feed = train_feed(cfg, batch, seq_len)
        exe = fluid.Executor(fluid.TPUPlace(0))
        losses, scope, setup_s, steady_s = run_train_steps(
            exe, main, startup, avg_cost, feed, steps)
        check(losses[-1] < losses[0],
              f"train s={seq_len}: loss did not fall: {losses}")
        calls = None
        if on_chip:
            # forward kernels at the short shape; forward + dq + dkv past
            # PALLAS_BWD_MIN_L — their presence in the COMPILED step, not
            # the absence of an exception, shows no kernel gave way
            with fluid.scope_guard(scope):
                calls = exe.compiled_hlo(main, feed=feed,
                                         fetch_list=[avg_cost]
                                         ).count(CUSTOM_CALL)
            want = n_attn * (1 if i == 0 else 3)    # the forward once an op
            check(calls == want,
                  f"train s={seq_len}: {calls} tpu_custom_call in the "
                  f"compiled step, expected {want}")
        say("train", batch=batch, seq_len=seq_len, losses=losses,
            setup_s=round(setup_s, 1), steady_s=round(steady_s, 2),
            steady_steps=steps - 1, tpu_custom_calls=calls)
        if first is None:
            first = losses
    return first


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def phase_kernels(cfg, pallas_impl: str):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import ragged_decode_attention

    m, s = cfg["model"], cfg["serve"]
    b, h, d = s["n_slots"], m["n_head"], m["d_key"]
    ps, n_layer, layer = s["page_size"], 2, 1
    n_pages = -(-s["src_len"] // ps)
    ctx = n_pages * ps
    rows = (b * n_pages + 1) * n_layer * 2
    table = jnp.asarray(
        1 + np.arange(b * n_pages, dtype=np.int32).reshape(b, n_pages))
    worst = 0.0
    n_cases = 0
    for dtype in ("float32", "bfloat16", "int8"):
        rng = np.random.RandomState(SEED)
        scales = None
        if dtype == "int8":
            pool = jnp.asarray(
                rng.randint(-127, 128, (rows, ps, h * d)).astype(np.int8))
            scales = jnp.asarray(
                (rng.rand(1, rows, ps).astype(np.float32) + 0.5) / 127.0)
        else:
            pool = jnp.asarray(
                rng.randn(rows, ps, h * d).astype(np.float32)).astype(dtype)
        for c in (1, s["chunk_size"]):
            q = jnp.asarray(rng.randn(b, c, h, d).astype(np.float32))
            # lane 0 is dead; the others end mid-page, full, and short
            lengths = np.array([0, ctx, ctx - ps // 2 - 1,
                                max(c, ps + 3)][:b], np.int32)
            base = jnp.asarray(np.maximum(lengths - c, 0).astype(np.int32))
            lengths = jnp.asarray(lengths)
            for causal in (True, False):
                def call(impl):
                    f = jax.jit(lambda *a: ragged_decode_attention(
                        a[0], a[1], table, a[2], a[3], layer=layer,
                        n_layer=n_layer, causal=causal, impl=impl,
                        scales=scales))
                    return np.asarray(f(q, pool, lengths, base))
                ref, got = call("xla"), call(pallas_impl)
                tag = f"kernels {dtype} C={c} causal={causal}"
                check(got.shape == (b, c, h, d) and np.isfinite(got).all(),
                      f"{tag}: bad output")
                check(not got[0].any(), f"{tag}: dead lane is not zero")
                rel = float(np.abs(got - ref).max() / np.abs(ref).max())
                check(rel <= cfg["kernel_rel_tol"],
                      f"{tag}: pallas vs xla differ by {rel:.2e} of "
                      f"max|ref| (tolerance {cfg['kernel_rel_tol']})")
                worst = max(worst, rel)
                n_cases += 1
    say("kernels", impl=pallas_impl, cases=n_cases, page_size=ps,
        worst_rel_err=float(f"{worst:.3g}"),
        rel_tol=cfg["kernel_rel_tol"])


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _generate(addr: str, body: dict):
    """POST /v1/generate -> tokens (urllib raises on any status but 200).
    A streamed answer is one JSON line per token and a final ``done``
    line."""
    req = urllib.request.Request(
        f"http://{addr}/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        raw = resp.read().decode()
    if not body.get("stream"):
        return json.loads(raw)["tokens"]
    lines = [json.loads(ln) for ln in raw.splitlines() if ln.strip()]
    check(lines and lines[-1].get("done") and "error" not in lines[-1],
          f"serve: stream ended badly: {lines[-1:]}")
    return [ln["token"] for ln in lines[:-1]]


def phase_serve(cfg, on_chip: bool):
    """-> what the mesh phase needs to serve the same artifact again."""
    from paddle_tpu import fluid
    from paddle_tpu.serving import PagedTransformerGenerator
    from paddle_tpu.serving.gateway import (Gateway, GatewayServer,
                                            ModelRegistry)

    s, vocab = cfg["serve"], cfg["vocab"]
    n_slots, n_req = s["n_slots"], s["n_requests"]
    t0 = time.perf_counter()
    gen = PagedTransformerGenerator(
        vocab, vocab, max_length=s["src_len"] + 1, src_len=s["src_len"],
        max_out_len=s["max_out_len"], page_size=s["page_size"],
        chunk_size=s["chunk_size"], param_prefix="tfbase", **cfg["model"])
    gen.init_params(seed=SEED)

    rng = np.random.RandomState(SEED)
    lens = np.linspace(s["min_prompt"], s["src_len"], n_req).astype(int)
    prompts = [rng.randint(2, vocab, n).tolist() for n in lens]
    max_news = [max(1, s["max_out_len"] // (1 + i % 3))
                for i in range(n_req)]

    # the reference: the generator's own greedy decode, n_slots prompts
    # at a time — the lane count, and so the executable, the gateway
    # serves with
    ref = []
    for g in range(0, n_req, n_slots):
        idx = [min(g + j, n_req - 1) for j in range(n_slots)]
        src = np.zeros((n_slots, s["src_len"]), np.int64)
        for row, i in enumerate(idx):
            src[row, :lens[i]] = prompts[i]
        out = gen.greedy(src, lens[idx], max_new=s["max_out_len"],
                         stop_at_end=False)
        ref.extend(out[:n_req - g].tolist())
    ref = [row[:n] for row, n in zip(ref, max_news)]
    # random weights may emit any id: end-of-sequence is one these
    # decodes never produce, so every request runs its max_new
    emitted = {t for row in ref for t in row}
    gen.end_id = next(i for i in range(1, vocab) if i not in emitted)

    root = os.path.join(OUT_DIR, "models")
    shutil.rmtree(root, ignore_errors=True)
    ModelRegistry.save_generator_artifact(gen, root, "tfbase", "1")
    del gen

    registry = ModelRegistry(root=root)
    gw = Gateway(registry=registry, n_slots=n_slots,
                 max_new_tokens=s["max_out_len"])
    gw.load_model("tfbase", "1")            # builds, uploads, warms
    inst = registry.instance("tfbase")
    warm_misses = inst.exe.cache_stats()["executable"]["misses"]
    warm_pool = inst.scope.find_var(f"{inst.prefix}@kv_pool")
    server = GatewayServer(gw, port=0)
    addr = server.start()
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    try:
        bodies = [{"model": "tfbase", "prompt": prompts[i],
                   "max_new": max_news[i], "stream": i == 1}
                  for i in range(n_req)]
        with concurrent.futures.ThreadPoolExecutor(n_req) as clients:
            answers = list(clients.map(lambda b: _generate(addr, b),
                                       bodies))
    finally:
        server.stop(drain=True)
    traffic_s = time.perf_counter() - t1

    for i, tokens in enumerate(answers):
        check(len(tokens) == max_news[i],
              f"serve: request {i} returned {len(tokens)} tokens, asked "
              f"{max_news[i]}")
        check(tokens == ref[i],
              f"serve: request {i} tokens differ from the generator's "
              f"greedy decode: {tokens} != {ref[i]}")
    stats = gw.sched.stats()
    check(stats["failed"] == 0 and stats["finished"] == n_req,
          f"serve: scheduler stats {stats}")
    check(stats["peak_in_flight"] >= min(4, n_slots),
          f"serve: only {stats['peak_in_flight']} requests in flight")
    exe_stats = inst.exe.cache_stats()
    check(exe_stats["executable"]["misses"] == warm_misses,
          "serve: an executable compiled after warm-up")
    check(warm_pool.is_deleted(),
          "serve: the KV pool was not donated to the decode step")
    calls = None
    if on_chip:
        calls = inst.compiled_step_hlo().count(CUSTOM_CALL)
        want = 3 * cfg["model"]["n_layer"]
        check(calls >= want, f"serve: {calls} tpu_custom_call in the "
                             f"compiled step, expected >= {want}")
    say("serve", requests=n_req, n_slots=n_slots, streamed=1,
        peak_in_flight=stats["peak_in_flight"], steps=stats["steps"],
        decoded_tokens=sum(max_news), failed=stats["failed"],
        misses_after_warmup=0, pool_donated=True,
        setup_s=round(setup_s, 1), traffic_s=round(traffic_s, 2),
        tpu_custom_calls=calls, end_id=inst.end_id)
    return {"root": root, "prompts": prompts, "lens": lens,
            "ref": ref, "max_out_len": s["max_out_len"]}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_train(cfg, on_chip: bool, ref_losses, axes, four):
    """The train step of phase_train's first shape under ``axes``."""
    import jax

    from paddle_tpu import fluid, parallel

    batch, seq_len, _ = cfg["train"][0]
    steps = 3
    mesh = parallel.make_mesh(axes)
    main, startup, avg_cost = build_train(cfg, seq_len,
                                          mp_shard="mp" in axes)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)                    # one chip: same init
        with parallel.mesh_guard(mesh):
            # feeds laid out the way run() would, so their span is
            # checked on real arrays
            feed = {n: jax.device_put(v, parallel.feed_sharding(mesh, v))
                    for n, v in train_feed(cfg, batch, seq_len).items()}
            losses = [float(exe.run(main, feed=feed,
                                    fetch_list=[avg_cost])[0])
                      for _ in range(steps)]
            hlo = exe.compiled_hlo(main, feed=feed, fetch_list=[avg_cost])
    tag = f"multichip train {axes}"
    src = feed["src_word"]
    check(set(src.sharding.device_set) == four and
          src.addressable_shards[0].data.shape[0] == batch // axes["dp"],
          f"{tag}: feed not split over dp")
    state = [v for v in scope.vars.values() if isinstance(v, jax.Array)]
    check(state and all(set(v.sharding.device_set) == four for v in state),
          f"{tag}: state does not span the four devices")
    if "mp" in axes:
        check(any(not v.sharding.is_fully_replicated for v in state),
              f"{tag}: no parameter is sharded over mp")
    # every parameter's gradient crosses dp — the whole set under dp=4,
    # each shard's half of the mp-split ones under 2 x 2 — in bf16 at
    # least: under amp the matmul weights' gradients are reduced in
    # bf16, the embeddings' and the vocab head's in f32 (my chip run,
    # PR 21: 290.6 MB for 94.7 M parameters)
    n_params = sum(int(np.prod(p.shape))
                   for p in main.global_block().all_parameters())
    per_kind, _ = fluid.Executor.collectives_in_hlo(hlo)
    reduces = per_kind.get("all-reduce", {"count": 0, "payload_bytes": 0})
    floor = (0.45 if "mp" in axes else 0.9) * 2 * n_params
    check(reduces["payload_bytes"] >= floor,
          f"{tag}: all-reduces move {reduces['payload_bytes']} bytes, the "
          f"gradients alone need {floor:.0f}: {per_kind}")
    check(np.allclose(losses, ref_losses[:steps], rtol=MESH_LOSS_REL_TOL),
          f"{tag}: losses {losses} vs one chip {ref_losses[:steps]}")
    calls = hlo.count(CUSTOM_CALL) if on_chip else None
    if on_chip:
        want = 3 * cfg["model"]["n_layer"]
        check(calls >= want, f"{tag}: {calls} tpu_custom_call in the "
                             f"partitioned step, expected >= {want}")
    say("multichip_train", mesh=axes, losses=losses,
        one_chip_losses=ref_losses[:steps],
        all_reduce_count=reduces["count"],
        all_reduce_bytes=reduces["payload_bytes"], parameters=n_params,
        tpu_custom_calls=calls)


def mesh_serve(cfg, on_chip: bool, served, four):
    """The artifact the gateway just served, loaded once more on one
    chip and tensor-parallel over four.  One decode step after the same
    prefill leaves both KV pools comparable row for row: they hold every
    layer's K/V of every prompt token (chunked causal prefill) and of
    the first decode position (whose upper layers sit on the C = 1 self-
    and cross-attention below them)."""
    from paddle_tpu import fluid
    from paddle_tpu.serving.gateway import ModelRegistry

    axes = {"batch": 1, "model": 4}
    n = cfg["serve"]["n_slots"]
    lens = served["lens"][:n]
    served_first = [row[0] for row in served["ref"][:n]]
    first, pools = {}, {}
    for name, overrides in (("one", {}), ("tp", {"mesh_axes": axes})):
        registry = ModelRegistry(root=served["root"])
        registry.load("tfbase", "1", **overrides)
        inst = registry.instance("tfbase")
        src = np.zeros((n, inst.src_len), np.int64)
        for row in range(n):
            src[row, :lens[row]] = served["prompts"][row]
        first[name] = inst.greedy(src, lens, max_new=1,
                                  stop_at_end=False)[:, 0].tolist()
        pool = inst.scope.find_var(f"{inst.prefix}@kv_pool")
        pools[name] = np.asarray(pool).astype(np.float32)
    # inst, src and pool are now the tensor-parallel load's
    check(set(pool.sharding.device_set) == four and
          pool.addressable_shards[0].data.shape[-1]
          == cfg["model"]["n_head"] * cfg["model"]["d_key"] // 4,
          "multichip serve: pool not split by heads over four devices")
    check(first["tp"] == first["one"] == served_first,
          f"multichip serve: first tokens {first} vs the served "
          f"{served_first}")
    pool_diff = float(np.abs(pools["tp"] - pools["one"]).max()
                      / np.abs(pools["one"]).max())
    check(pool_diff <= cfg["kernel_rel_tol"],
          f"multichip serve: KV pools differ by {pool_diff:.2e} of "
          f"max|pool| (tolerance {cfg['kernel_rel_tol']})")
    # full-length greedy, REPORTED not checked: with random weights the
    # top two logits are often a rounding error apart, and one flip
    # changes every later token (my chip run, PR 21: 1 lane of 4 left
    # the one-chip tokens at its second token)
    out = inst.greedy(src, lens, max_new=served["max_out_len"],
                      stop_at_end=False).tolist()
    same = [f"{sum(a == b for a, b in zip(out[row], want))}/{len(want)}"
            for row, want in enumerate(served["ref"][:n])]
    hlo = inst.compiled_step_hlo()
    calls = hlo.count(CUSTOM_CALL) if on_chip else None
    if on_chip:
        want = 3 * cfg["model"]["n_layer"]
        check(calls >= want, f"multichip serve: {calls} tpu_custom_call "
                             f"in the partitioned step, expected >= {want}")
    per_kind, _ = fluid.Executor.collectives_in_hlo(hlo)
    say("multichip_serve", mesh=axes, lanes=n, first_tokens_equal=True,
        pool_rel_diff=float(f"{pool_diff:.3g}"),
        tokens_equal_one_chip=same,
        all_reduce_count=per_kind.get("all-reduce", {}).get("count", 0),
        tpu_custom_calls=calls)


def phase_multichip(cfg, on_chip: bool, ref_losses, served):
    import jax

    n_dev = len(jax.devices())
    if n_dev < 4:
        print(f"multichip: not run, {n_dev} device", flush=True)
        return
    four = set(jax.devices()[:4])
    for axes in ({"dp": 4}, {"dp": 2, "mp": 2}):
        mesh_train(cfg, on_chip, ref_losses, axes, four)
    mesh_serve(cfg, on_chip, served, four)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the same phases tiny on a host without a "
                         "chip (interpret-mode kernels; output says "
                         "platform: cpu; proves control flow, measures "
                         "nothing)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    print(f"device: platform: {device['platform']} device_kind: "
          f"{device['kind']} count: {device['count']} jax {jax.__version__} "
          f"jaxlib {jaxlib.__version__} libtpu {libtpu}", flush=True)
    on_chip = dev.platform == "tpu"
    if on_chip == args.rehearse_cpu:
        print("chip_smoke: " + (
            "--rehearse-cpu is for hosts without a chip; this one has a TPU"
            if on_chip else
            "no TPU (jax.devices()[0].platform = "
            f"{dev.platform!r}); nothing was built.  --rehearse-cpu walks "
            "the phases tiny on the CPU."), file=sys.stderr)
        return 1

    import paddle_tpu  # noqa: F401  (places the compile cache)
    from paddle_tpu import native

    cfg = FULL if on_chip else REHEARSAL
    pallas_impl = "pallas"
    if not on_chip:
        # the auto-pick would take the XLA path off-chip; the rehearsal
        # walks the kernels' code instead, interpreted
        # (import_module: the package re-exports a FUNCTION under the
        # module's own name, which `import ... as` would pick up)
        fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
        pallas_impl = "pallas_interpret"
        fa.default_impl = lambda: pallas_impl
        fa.PALLAS_BWD_MIN_L = cfg["train"][1][1]
    os.makedirs(OUT_DIR, exist_ok=True)
    entries0 = cache_entries()
    placed_by = ("JAX_COMPILATION_CACHE_DIR"
                 if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 else "placed by paddle_tpu")
    print(f"compile cache: {jax.config.jax_compilation_cache_dir} "
          f"({placed_by}), {entries0} entries; native.available(): "
          f"{native.available()}", flush=True)

    t0 = time.perf_counter()
    ref_losses = phase_train(cfg, on_chip)
    phase_kernels(cfg, pallas_impl)
    served = phase_serve(cfg, on_chip)
    phase_multichip(cfg, on_chip, ref_losses, served)
    print(f"compile cache: {entries0} -> {cache_entries()} entries; "
          f"total {time.perf_counter() - t0:.0f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
