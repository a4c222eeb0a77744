#!/usr/bin/env bash
# Lint gate (PR 3 satellite): ruff over paddle_tpu/ (config in
# pyproject.toml) + a plint sweep over freshly built book programs.
#
#   tools/lint.sh            # run everything available
#   tools/lint.sh --ruff     # ruff only
#   tools/lint.sh --plint    # program lint only
#   tools/lint.sh --sync     # concurrency lint + lock-order graph only
#
# ruff is optional in the hermetic CI container (no network installs);
# when absent we warn and still run the program linter, which needs
# nothing beyond the repo's own Python deps.

set -u
cd "$(dirname "$0")/.."

want_ruff=1
want_plint=1
want_sync=1
case "${1:-}" in
  --ruff)  want_plint=0; want_sync=0 ;;
  --plint) want_ruff=0; want_sync=0 ;;
  --sync)  want_ruff=0; want_plint=0 ;;
  "") ;;
  *) echo "usage: tools/lint.sh [--ruff|--plint|--sync]" >&2; exit 64 ;;
esac

rc=0

if [ "$want_sync" = 1 ]; then
  # concurrency lint (ISSUE 13): raw threading primitives outside
  # utils/sync.py, blocking I/O lexically under a lock, predicate-free
  # condition waits — errors fail the gate
  echo "== syncheck (concurrency lint) over paddle_tpu/"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m paddle_tpu.tools.syncheck paddle_tpu || rc=1

  # the fleet package (ISSUE 16) proxies HTTP while tracking rotation
  # state — the explicit second sweep makes an I/O-under-lock
  # regression there unmissable
  echo "== syncheck over paddle_tpu/serving/fleet/"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m paddle_tpu.tools.syncheck paddle_tpu/serving/fleet \
      paddle_tpu/tools/fleet.py || rc=1

  # the elastic pod control plane (ISSUE 19) mixes HTTP handlers, a
  # heartbeat thread and the coordinator state lock — the explicit
  # sweep makes a raw-primitive or I/O-under-lock regression there
  # unmissable
  echo "== syncheck over paddle_tpu/parallel/"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m paddle_tpu.tools.syncheck paddle_tpu/parallel || rc=1

  # the KV tier + session store (ISSUE 20) move device pages and disk
  # artifacts from the serve loop while the scheduler lock guards the
  # bookkeeping — suspend d2h and artifact fsync MUST stay off that
  # lock; the explicit sweep makes an I/O-under-lock regression in the
  # tier path unmissable
  echo "== syncheck over the tiered-KV serving modules"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m paddle_tpu.tools.syncheck paddle_tpu/serving/paging.py \
      paddle_tpu/serving/paged_decoder.py \
      paddle_tpu/serving/sessions.py \
      paddle_tpu/serving/scheduler.py || rc=1

  # smoke-run the real scheduler/gateway/journal stack with runtime
  # order checking ON and dump the observed lock-order graph as an
  # artifact (SYNC_GRAPH_OUT overrides the path) — the graph is the
  # living version of the README rank table
  # per-run paths: a fixed /tmp name would let two concurrent lint
  # runs on one host append to each other's smoke journal (spurious
  # pending()!=[] failures) or interleave graph writes
  graph_out="${SYNC_GRAPH_OUT:-/tmp/paddle_tpu_sync_graph.$$.json}"
  smoke_journal="$(mktemp /tmp/paddle_tpu_sync_smoke.XXXXXX.jsonl)"
  echo "== sync smoke: lock-order graph -> $graph_out"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python - "$graph_out" "$smoke_journal" <<'EOF' || rc=1
import sys

import numpy as np

from paddle_tpu.serving.gateway import Gateway
from paddle_tpu.utils import sync


class Echo:
    start_id, end_id = 0, 1
    src_len = 64

    def __init__(self):
        self.n, self.slot_val = 0, {}

    def open_slots(self, n):
        self.n = n

    def admit_slot(self, slot, prompt, **_):
        self.slot_val[slot] = int(np.asarray(prompt).reshape(-1)[0])
        return len(np.asarray(prompt).reshape(-1))

    def clear_slot(self, slot):
        self.slot_val.pop(slot, None)

    def step_slots(self, tokens, pos, src_len):
        return np.array([self.slot_val.get(i, 7777)
                         for i in range(self.n)], np.int64)


sync.registry().reset()
sync.enable_checking()
gw = Gateway(n_slots=2, max_new_tokens=4, journal_path=sys.argv[2])
gw.load_model("m", "1", instance=Echo())
gw.serve()
reqs = [gw.submit("m", [40 + i]) for i in range(8)]
for r in reqs:
    assert r.wait(30), "smoke request stalled"
gw.swap_model("m", "2", instance=Echo())
gw.shutdown(drain=True)
assert gw.journal.pending() == []
g = sync.registry().export_graph(sys.argv[1])
assert g["violations"] == 0, f"lock-order violations: {g}"
assert g["edges"], "smoke run recorded no lock-order edges"
print(f"sync smoke: {len(g['nodes'])} locks, {len(g['edges'])} edges, "
      f"0 violations")
sync.disable_checking()
EOF
  rm -f "$smoke_journal"

  # pod smoke (ISSUE 19): two REAL subprocess hosts rendezvous through
  # a CoordinatorServer, train 6 lockstep steps with mean-reduced
  # gradients, and must finish bitwise identical with the coordinated
  # manifest committed at the final step — the minimal end-to-end pass
  # over the elastic control plane on every lint run
  echo "== pod smoke: 2 subprocess hosts through the coordinator"
  pod_tmp="$(mktemp -d)"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - "$pod_tmp" <<'EOF' || rc=1
import os, subprocess, sys

tmpdir = sys.argv[1]
from paddle_tpu.fluid.checkpoint import PodCheckpointManager
from paddle_tpu.parallel import CoordinatorServer

WORKER = '''
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from paddle_tpu.parallel import PodClient
from paddle_tpu.resilience import ResilientTrainer

addr, ckpt, host = sys.argv[1:4]
params = {}
w_true = np.arange(4, dtype=np.float32)[:, None]

def read_chunk(step, rank, world):
    r = np.random.RandomState(step)
    xs = r.randn(8, 4).astype(np.float32)
    return xs[rank::world], (xs @ w_true)[rank::world]

def train_step(rec, step):
    xs, ys = rec
    g = 2.0 * xs.T @ (xs @ params["w"] - ys) / len(xs)
    return True, {"w": g.astype(np.float32)}

trainer = ResilientTrainer(
    ckpt, coordinator=PodClient(addr, host, poll_interval=0.01),
    read_chunk=read_chunk,
    apply_update=lambda red, step: params.update(
        w=(params["w"] - 0.05 * red["w"]).astype(np.float32)),
    state_get=lambda: dict(params),
    state_set=lambda items: params.update(items),
    save_interval_steps=3, rendezvous_deadline=60.0,
    step_deadline=60.0, heartbeat_interval=0.2)
final = trainer.run(train_step,
                    init_fn=lambda: params.update(
                        w=np.zeros((4, 1), np.float32)),
                    max_steps=6)
assert final == 6, final
print(params["w"].tobytes().hex())
'''
script = os.path.join(tmpdir, "pod_worker.py")
open(script, "w").write(WORKER)
srv = CoordinatorServer(world_min=1, world_target=2)
addr = srv.start()
try:
    env = dict(os.environ,
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
               PYTHONPATH=os.getcwd() + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen(
        [sys.executable, script, addr, os.path.join(tmpdir, "pod"),
         f"h{i}"], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for i in range(2)]
    outs = [p.communicate(timeout=180) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-2000:]
    finals = [out.strip().splitlines()[-1] for out, _ in outs]
    assert finals[0] == finals[1], "pod hosts diverged"
    assert srv.status()["last_committed"] == 6, srv.status()
finally:
    srv.stop()
assert PodCheckpointManager(os.path.join(tmpdir, "pod")) \
    .latest_committed() == 6
print("pod smoke: 2 hosts, 6 lockstep steps, params bitwise "
      "identical, manifest committed @6")
EOF
  rm -rf "$pod_tmp"
fi

if [ "$want_ruff" = 1 ]; then
  # paddle_tpu/ covers the observability package (ISSUE 8) too — the
  # explicit second sweep just makes a regression there unmissable
  if command -v ruff >/dev/null 2>&1; then
    echo "== ruff check paddle_tpu/"
    ruff check paddle_tpu/ || rc=1
    ruff check paddle_tpu/observability/ paddle_tpu/tools/obs.py || rc=1
  elif python -c "import ruff" >/dev/null 2>&1; then
    echo "== python -m ruff check paddle_tpu/"
    python -m ruff check paddle_tpu/ || rc=1
    python -m ruff check paddle_tpu/observability/ \
      paddle_tpu/tools/obs.py || rc=1
  else
    echo "== ruff not installed; skipping style lint (pyproject.toml holds the config)"
  fi
fi

if [ "$want_plint" = 1 ]; then
  echo "== plint over the book programs (forward + backward + optimizer)"
  tmpdir="$(mktemp -d)"
  trap 'rm -rf "$tmpdir"' EXIT
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python - "$tmpdir" <<'EOF' || rc=1
# Build each book model, serialize it, and emit <name>.json + <name>.fetch
# for the CLI sweep below — the same programs tests/test_book.py trains.
import sys, os

tmpdir = sys.argv[1]
from paddle_tpu import fluid
from paddle_tpu.models import recognize_digits, word2vec, image_classification


def build(name, fn):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        fetch = fn()
    with open(os.path.join(tmpdir, name + ".json"), "wb") as f:
        f.write(main.desc.serialize_to_string())
    with open(os.path.join(tmpdir, name + ".fetch"), "w") as f:
        f.write("".join(v.name + "\n" for v in fetch))


def digits_conv():
    img = fluid.layers.data(name="img", shape=[1, 28, 28], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    _, avg_cost, acc = recognize_digits.conv_net(img, label)
    fluid.optimizer.Adam(learning_rate=0.01).minimize(avg_cost)
    return [avg_cost, acc]


def w2v():
    words = [fluid.layers.data(name=f"w{i}", shape=[1], dtype="int64")
             for i in range(5)]
    avg_cost, _ = word2vec.ngram_model(words, 30, embed_size=8,
                                       hidden_size=32)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(avg_cost)
    return [avg_cost]


def resnet():
    img = fluid.layers.data(name="img", shape=[3, 32, 32], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    predict = image_classification.resnet_cifar10(img, depth=8, class_num=4)
    cost = fluid.layers.cross_entropy(input=predict, label=label)
    avg_cost = fluid.layers.mean(cost)
    fluid.optimizer.Momentum(learning_rate=0.02, momentum=0.9).minimize(
        avg_cost)
    return [avg_cost]


build("digits_conv", digits_conv)
build("word2vec", w2v)
build("resnet_cifar", resnet)

# serving sweep (ISSUE 5): the KV-cache decode-step program — cache_write /
# decode_attention ops + the in-graph greedy head — must stay analyzer-clean
from paddle_tpu.serving import TransformerGenerator

gen = TransformerGenerator(30, 30, n_layer=2, n_head=2, d_key=4, d_value=4,
                           d_model=16, d_inner_hid=32, max_length=64,
                           src_len=8, max_out_len=8, param_prefix="tfs",
                           place=fluid.CPUPlace())
step_prog, _, next_ids, _ = gen._step
with open(os.path.join(tmpdir, "serving_step.json"), "wb") as f:
    f.write(step_prog.desc.serialize_to_string())
with open(os.path.join(tmpdir, "serving_step.fetch"), "w") as f:
    f.write(next_ids.name + "\n")

# observability sweep (ISSUE 8): instrumentation must not perturb the
# compiled program — the decode-step program built while the tracer is
# recording must serialize BYTE-IDENTICAL to one built with telemetry
# off, and the instrumented build goes through the analyzer like any
# other program
from paddle_tpu.observability import tracing as _obs_tracing

_tr = _obs_tracing.tracer()
_was = _tr.enabled
_tr.disable()
gen_bare = TransformerGenerator(30, 30, n_layer=2, n_head=2, d_key=4,
                                d_value=4, d_model=16, d_inner_hid=32,
                                max_length=64, src_len=8, max_out_len=8,
                                param_prefix="tfs",
                                place=fluid.CPUPlace())
_tr.enabled = True
gen_inst = TransformerGenerator(30, 30, n_layer=2, n_head=2, d_key=4,
                                d_value=4, d_model=16, d_inner_hid=32,
                                max_length=64, src_len=8, max_out_len=8,
                                param_prefix="tfs",
                                place=fluid.CPUPlace())
_tr.enabled = _was
bare_bytes = gen_bare._step[0].desc.serialize_to_string()
inst_bytes = gen_inst._step[0].desc.serialize_to_string()
assert bare_bytes == inst_bytes, \
    "telemetry perturbed the compiled decode-step program"
with open(os.path.join(tmpdir, "serving_step_instrumented.json"), "wb") as f:
    f.write(inst_bytes)
with open(os.path.join(tmpdir, "serving_step_instrumented.fetch"), "w") as f:
    f.write(gen_inst._step[2].name + "\n")

# paged sweep (ISSUE 6): the unified ragged decode-step program — chunked
# prefill tower + paged_cache_write / ragged_decode_attention / page-copy
# ops + greedy head, all in ONE dispatch — must also stay analyzer-clean
from paddle_tpu.serving import PagedTransformerGenerator

pgen = PagedTransformerGenerator(30, 30, n_layer=2, n_head=2, d_key=4,
                                 d_value=4, d_model=16, d_inner_hid=32,
                                 max_length=64, src_len=8, max_out_len=8,
                                 page_size=4, chunk_size=4, num_pages=32,
                                 param_prefix="tfpg",
                                 place=fluid.CPUPlace())
uni_prog, _, uni_ids, _ = pgen._unified
with open(os.path.join(tmpdir, "serving_ragged_step.json"), "wb") as f:
    f.write(uni_prog.desc.serialize_to_string())
with open(os.path.join(tmpdir, "serving_ragged_step.fetch"), "w") as f:
    f.write(uni_ids.name + "\n")

# quantized sweep (ISSUE 7): (a) a PTQ-rewritten pruned program —
# quantized_mul ops + int8 persistables + fp32 scale sidecars — and
# (b) the int8-KV unified decode-step program (quantized_paged_cache_write
# / scale-carrying ragged attention / quantized page copies) must both
# stay analyzer-clean
from paddle_tpu.fluid.transforms.quantize import quantize_program

qmain, qstartup = fluid.Program(), fluid.Program()
qscope = fluid.Scope()
with fluid.program_guard(qmain, qstartup), fluid.unique_name.guard():
    x = fluid.layers.data(name="x", shape=[6], dtype="float32")
    h = fluid.layers.fc(input=x, size=16, act="relu")
    y = fluid.layers.fc(input=h, size=4)
qexe = fluid.Executor(fluid.CPUPlace())
with fluid.scope_guard(qscope):
    qexe.run(qstartup)
qpruned = fluid.io.prune_program(qmain, [y])
stats = quantize_program(qpruned, qscope)
assert stats.quantized, "PTQ rewrite quantized nothing — sweep is vacuous"
with open(os.path.join(tmpdir, "quantized_pruned.json"), "wb") as f:
    f.write(qpruned.desc.serialize_to_string())
with open(os.path.join(tmpdir, "quantized_pruned.fetch"), "w") as f:
    f.write(y.name + "\n")

qgen = PagedTransformerGenerator(30, 30, n_layer=2, n_head=2, d_key=4,
                                 d_value=4, d_model=16, d_inner_hid=32,
                                 max_length=64, src_len=8, max_out_len=8,
                                 page_size=4, chunk_size=4, num_pages=32,
                                 param_prefix="tfqg", kv_dtype="int8",
                                 place=fluid.CPUPlace())
qprog, _, qids, _ = qgen._unified
with open(os.path.join(tmpdir, "serving_int8_ragged_step.json"), "wb") as f:
    f.write(qprog.desc.serialize_to_string())
with open(os.path.join(tmpdir, "serving_int8_ragged_step.fetch"), "w") as f:
    f.write(qids.name + "\n")

# tier sweep (ISSUE 20): the fixed-width page d2h/h2d copy-program
# pair — the ONLY device work KV tiering adds — must stay analyzer-
# clean and fully priced; the int8 generator's pair carries the fp32
# scale sidecar, so it covers the quantized gather/scatter ops too
tprogs = qgen._xfer()
tdown, tfetch = tprogs["down"]
with open(os.path.join(tmpdir, "kv_tier_download.json"), "wb") as f:
    f.write(tdown.desc.serialize_to_string())
with open(os.path.join(tmpdir, "kv_tier_download.fetch"), "w") as f:
    f.write("".join(v.name + "\n" for v in tfetch))
tup = tprogs["up"]
with open(os.path.join(tmpdir, "kv_tier_upload.json"), "wb") as f:
    f.write(tup.desc.serialize_to_string())
with open(os.path.join(tmpdir, "kv_tier_upload.fetch"), "w") as f:
    f.write(qgen._pool_name + "\n")

# sharded sweep (ISSUE 17): the tensor-parallel unified decode-step
# program — head-sharded QKV/O + column/row MLP partitions annotated on
# the descs, the pool partitioned on its head axis — must stay
# analyzer-clean, and the cost pass below prices it PER SHARD at
# --mesh-axis model=2 (no devices needed: desc-level build only)
from paddle_tpu.serving.paged_decoder import build_unified_program

sh_prog, _, sh_ids, _ = build_unified_program(
    pgen.cfg, src_len=8, max_out_len=8, page_size=4, num_pages=32,
    chunk_size=4, param_prefix="tfsh", shard_axis="model")
with open(os.path.join(tmpdir, "serving_sharded_ragged_step.json"),
          "wb") as f:
    f.write(sh_prog.desc.serialize_to_string())
with open(os.path.join(tmpdir, "serving_sharded_ragged_step.fetch"),
          "w") as f:
    f.write(sh_ids.name + "\n")

# speculative sweep (ISSUE 15): the target's k-token VERIFY program
# (per-lane token axis + logit-mask data feed) and the draft's
# constrained decode-step program must both stay analyzer-clean —
# they are what a speculative lane group actually dispatches
from paddle_tpu.serving.speculative import SpeculativeGenerator

sdraft = PagedTransformerGenerator(30, 30, n_layer=1, n_head=2, d_key=4,
                                   d_value=4, d_model=16, d_inner_hid=32,
                                   max_length=64, src_len=8, max_out_len=8,
                                   page_size=4, chunk_size=4, num_pages=32,
                                   param_prefix="tfdr",
                                   place=fluid.CPUPlace())
sgen = SpeculativeGenerator(pgen, sdraft, k=3)
vprog, _, v_ids, _ = sgen._verify
with open(os.path.join(tmpdir, "speculative_verify_step.json"), "wb") as f:
    f.write(vprog.desc.serialize_to_string())
with open(os.path.join(tmpdir, "speculative_verify_step.fetch"), "w") as f:
    f.write(v_ids.name + "\n")
dprog, _, d_ids, _ = sgen._draft_prog
with open(os.path.join(tmpdir, "speculative_draft_step.json"), "wb") as f:
    f.write(dprog.desc.serialize_to_string())
with open(os.path.join(tmpdir, "speculative_draft_step.fetch"), "w") as f:
    f.write(d_ids.name + "\n")

# gateway sweep (ISSUE 10): every program the registry builds for a
# loaded model version must stay analyzer-clean — round-trip a
# generator artifact AND an engine artifact through ModelRegistry.load
# and plint what the loaded instances will actually dispatch
from paddle_tpu.serving.gateway import ModelRegistry

groot = os.path.join(tmpdir, "model-store")
ModelRegistry.save_generator_artifact(pgen, groot, "gen", "1")
greg = ModelRegistry(root=groot, place=fluid.CPUPlace())
greg.load("gen", "1")
ginst = greg.instance("gen")
gw_prog, _, gw_ids, _ = ginst._unified
with open(os.path.join(tmpdir, "gateway_generator_step.json"), "wb") as f:
    f.write(gw_prog.desc.serialize_to_string())
with open(os.path.join(tmpdir, "gateway_generator_step.fetch"), "w") as f:
    f.write(gw_ids.name + "\n")

emain, estartup = fluid.Program(), fluid.Program()
escope = fluid.Scope()
with fluid.program_guard(emain, estartup), fluid.unique_name.guard():
    ex = fluid.layers.data(name="ex", shape=[6], dtype="float32")
    ey = fluid.layers.fc(input=ex, size=4)
eexe = fluid.Executor(fluid.CPUPlace())
with fluid.scope_guard(escope):
    eexe.run(estartup)
    fluid.io.save_versioned_inference_model(groot, "mlp", "1", ["ex"],
                                            [ey], eexe,
                                            main_program=emain)
greg.load("mlp", "1")
einst = greg.instance("mlp")
with open(os.path.join(tmpdir, "gateway_engine.json"), "wb") as f:
    f.write(einst.program.desc.serialize_to_string())
with open(os.path.join(tmpdir, "gateway_engine.fetch"), "w") as f:
    f.write("".join(str(v.name if hasattr(v, "name") else v) + "\n"
                    for v in einst.fetch_list))

# lifecycle sweep (ISSUE 12): the candidate artifacts the release
# controller publishes and gates — fp32 AND the int8-PTQ-manifested
# variant — must round-trip the staged publish, load through the
# registry, and dispatch analyzer-clean programs
lroot = os.path.join(tmpdir, "lifecycle-store")
with fluid.scope_guard(escope):
    fluid.io.save_versioned_inference_model(
        lroot, "cand", "1", ["ex"], [ey], eexe, main_program=emain)
    fluid.io.save_versioned_inference_model(
        lroot, "cand", "2", ["ex"], [ey], eexe, main_program=emain,
        manifest={"kind": "engine", "config": {"quantize": "int8"}})
lreg = ModelRegistry(root=lroot, place=fluid.CPUPlace())
for ver, tag in (("1", "fp32"), ("2", "int8")):
    lreg.load("cand", ver)
    linst = lreg.instance(f"cand@{ver}")
    if tag == "int8":
        assert linst.quantize == "int8" and linst.program is not emain, \
            "int8 manifest did not trigger the PTQ rewrite at load"
    with open(os.path.join(tmpdir, f"lifecycle_cand_{tag}.json"),
              "wb") as f:
        f.write(linst.program.desc.serialize_to_string())
    with open(os.path.join(tmpdir, f"lifecycle_cand_{tag}.fetch"),
              "w") as f:
        f.write("".join(str(v.name if hasattr(v, "name") else v) + "\n"
                        for v in linst.fetch_list))
EOF
  for prog in "$tmpdir"/*.json; do
    name="$(basename "$prog" .json)"
    fetch_args=""
    while read -r v; do
      [ -n "$v" ] && fetch_args="$fetch_args --fetch $v"
    done < "$tmpdir/$name.fetch"
    echo "-- plint $name"
    # shellcheck disable=SC2086
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m paddle_tpu.tools.plint "$prog" --quiet $fetch_args || rc=1
  done

  # cost sweep (ISSUE 11): the static cost family over the book
  # programs AND the paged int8 decode-step program AND the ISSUE 15
  # verify/constrained-draft programs — recompile-hazard errors fail
  # via the normal error exit, and an op one of these programs uses
  # with no registered cost rule fails via --fail-on (the analyzer
  # guessing about the flagship programs is a defect)
  for name in digits_conv word2vec resnet_cifar serving_int8_ragged_step \
              speculative_verify_step speculative_draft_step \
              kv_tier_download kv_tier_upload; do
    prog="$tmpdir/$name.json"
    [ -f "$prog" ] || { echo "-- plint --cost $name: MISSING"; rc=1; continue; }
    fetch_args=""
    while read -r v; do
      [ -n "$v" ] && fetch_args="$fetch_args --fetch $v"
    done < "$tmpdir/$name.fetch"
    echo "-- plint --cost $name"
    # shellcheck disable=SC2086
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m paddle_tpu.tools.plint "$prog" --cost --quiet \
        --assume-batch 64 --batch-bucket 8 \
        --fail-on unregistered-cost-rule --fail-on value-shape-op \
        $fetch_args || rc=1
  done

  # sharded cost sweep (ISSUE 17): the tensor-parallel unified
  # decode-step program priced PER SHARD at a model-axis of 2 — the
  # admission criterion the sharded gateway budgets with.  Recompile
  # hazards fail via the normal error exit; an op with no cost rule or
  # a collective the comms pass cannot price fails via --fail-on.
  name=serving_sharded_ragged_step
  prog="$tmpdir/$name.json"
  if [ -f "$prog" ]; then
    fetch_args=""
    while read -r v; do
      [ -n "$v" ] && fetch_args="$fetch_args --fetch $v"
    done < "$tmpdir/$name.fetch"
    echo "-- plint --cost $name (--mesh-axis model=2)"
    # shellcheck disable=SC2086
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m paddle_tpu.tools.plint "$prog" --cost --quiet \
        --assume-batch 64 --batch-bucket 8 --mesh-axis model=2 \
        --fail-on unregistered-cost-rule --fail-on value-shape-op \
        $fetch_args || rc=1
  else
    echo "-- plint --cost $name: MISSING"; rc=1
  fi

  # shardprop sweep (ISSUE 18): whole-program sharding inference over
  # the tensor-parallel decode-step program (model=2) and a dp book
  # training program — any resharding-hazard / partial-sum-unreduced /
  # dp-grad-divergence finding fails the gate
  name=serving_sharded_ragged_step
  prog="$tmpdir/$name.json"
  if [ -f "$prog" ]; then
    fetch_args=""
    while read -r v; do
      [ -n "$v" ] && fetch_args="$fetch_args --fetch $v"
    done < "$tmpdir/$name.fetch"
    echo "-- plint --shard $name (--mesh-axis model=2)"
    # shellcheck disable=SC2086
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m paddle_tpu.tools.plint "$prog" --shard --quiet \
        --mesh-axis model=2 $fetch_args || rc=1
  else
    echo "-- plint --shard $name: MISSING"; rc=1
  fi
  name=digits_conv
  prog="$tmpdir/$name.json"
  if [ -f "$prog" ]; then
    fetch_args=""
    while read -r v; do
      [ -n "$v" ] && fetch_args="$fetch_args --fetch $v"
    done < "$tmpdir/$name.fetch"
    echo "-- plint --shard $name (--mesh-axis dp=2)"
    # shellcheck disable=SC2086
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
      python -m paddle_tpu.tools.plint "$prog" --shard --quiet \
        --mesh-axis dp=2 --assume-batch 8 $fetch_args || rc=1
  else
    echo "-- plint --shard $name: MISSING"; rc=1
  fi

  # HLO-differential check (ISSUE 18): the inferred collective graph
  # must match what XLA actually emits — Executor.collective_analysis
  # on a 4-virtual-device CPU mesh, op-for-op (equal counts AND equal
  # payload bytes per kind, rel_err 0.0) for a sharded decode step and
  # a dp-sharded training step
  echo "== shardprop HLO differential (4 virtual devices)"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    XLA_FLAGS="--xla_force_host_platform_device_count=4 ${XLA_FLAGS:-}" \
    python - <<'EOF' || rc=1
import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid.analysis.shardprop import (compare_collectives,
                                                 infer_sharding)
from paddle_tpu.parallel import mesh as pmesh
from paddle_tpu.parallel.transpiler import DistributeTranspiler
from paddle_tpu.serving import PagedTransformerGenerator


def gate(tag, prog, mesh_axes, feed, fetch_list, exe, scope, mesh,
         mode, assume_batch):
    with fluid.scope_guard(scope), pmesh.mesh_guard(mesh):
        meas = exe.collective_analysis(prog, feed=feed,
                                       fetch_list=fetch_list, mode=mode)
    pred = infer_sharding(
        prog, options={"mesh_axes": mesh_axes,
                       "assume_batch": assume_batch},
        fetch=[getattr(v, "name", v) for v in fetch_list])
    errs = [f.render() for f in pred.findings if f.severity == "error"]
    assert not errs, f"{tag}: {errs}"
    cmp = compare_collectives(pred.per_kind(), meas["per_kind"])
    assert cmp["match"] and cmp["rel_err"] == 0.0, (
        f"{tag}: rel_err={cmp['rel_err']} predicted={pred.per_kind()} "
        f"measured={meas['per_kind']}")
    print(f"{tag}: rel_err 0.0, "
          + ", ".join(f"{k}x{int(v['count'])}"
                      for k, v in sorted(pred.per_kind().items())))


ma = {"batch": 1, "model": 2}
g = PagedTransformerGenerator(30, 30, n_layer=2, n_head=2, d_key=4,
                              d_value=4, d_model=16, d_inner_hid=32,
                              max_length=64, src_len=8, max_out_len=8,
                              page_size=4, chunk_size=4, num_pages=32,
                              param_prefix="tfsh", mesh_axes=ma)
g.init_params(seed=1)
g.open_slots(2)
prog, _, next_ids, _ = g._unified
feed = g._step_feed()
gate("decode-step model=2", prog, ma, feed, [next_ids], g.exe,
     g.scope, g.mesh, "infer", 2)

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup), fluid.unique_name.guard():
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=x, size=32, act="relu")
    p = fluid.layers.fc(input=h, size=4, act="softmax")
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=p, label=y))
    opt_ops, pg = fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
t = DistributeTranspiler()
t.transpile(optimize_ops=opt_ops, params_grads=pg, trainers=4,
            program=main, mesh_axes={"dp": 4})
exe = fluid.Executor(fluid.TPUPlace(0))
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe.run(startup)
rng = np.random.RandomState(3)
feed = {"x": rng.rand(8, 16).astype("float32"),
        "y": rng.randint(0, 4, (8, 1)).astype("int64")}
gate("training dp=4", t.get_trainer_program(), {"dp": 4}, feed,
     [loss], exe, scope, pmesh.make_mesh({"dp": 4}), "train", 8)
EOF
fi

exit $rc
