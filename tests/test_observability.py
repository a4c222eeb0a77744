"""Observability tests (ISSUE 8): registry correctness under concurrent
writers, Prometheus exposition golden format, the per-request span
timeline of a seeded scheduler run, Chrome-trace schema sanity, and the
/metrics + /healthz + /statusz endpoint round-trips (including a live
scrape during a serving run)."""

import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.observability import (MetricsRegistry, ObservabilityServer,
                                      Sample, Tracer, registry, tracer)
from paddle_tpu.serving import ContinuousBatchingScheduler, PageAllocator


class FakeModel:
    """Minimal slot model (scheduler protocol): every lane emits token 5
    until max_new_tokens retires it — deterministic, no device work."""

    start_id, end_id = 0, 1

    def __init__(self, n=0):
        self.n = n

    def open_slots(self, n):
        self.n = n

    def admit_slot(self, slot, prompt):
        return len(prompt)

    def clear_slot(self, slot):
        pass

    def step_slots(self, tokens, pos, src_len):
        return np.full(self.n, 5, np.int64)

    def shard_plan(self):
        # mesh shape the scrape exposes per-shard (ISSUE 17): the
        # collector emits one shard_pool_bytes sample per model shard
        return {"mesh_axes": {"batch": 1, "model": 2},
                "shard_axis": "model", "n_model_shards": 2,
                "pool_bytes_per_shard": 4096.0}


# -- registry ----------------------------------------------------------------

def test_counter_concurrent_writers_exact():
    """N threads x K increments lose nothing (the whole point of the
    per-child lock: scheduler thread, watchdog, submitters all write)."""
    reg = MetricsRegistry()
    c = reg.counter("t_total", "t", labels=("who",))
    h = reg.histogram("t_lat", "t")
    n_threads, k = 8, 500

    def work(i):
        child = c.labels(who=f"w{i % 2}")
        for _ in range(k):
            child.inc()
            h.observe(0.01)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(c.labels(who=f"w{i}").value for i in range(2))
    assert total == n_threads * k
    _, _, count = h.labels().snapshot()
    assert count == n_threads * k


def test_instrument_type_and_label_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("x_total", "x", labels=("a",))
    with pytest.raises(ValueError):
        reg.gauge("x_total")                    # kind conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("b",))   # label-set conflict
    with pytest.raises(ValueError):
        reg.counter("bad name")                 # invalid name
    with pytest.raises(ValueError):
        reg.counter("y_total").inc(-1)          # counters only go up


def test_collector_weak_owner_and_accumulation():
    """Two collectors agreeing on (name, labels) SUM; a dead owner's
    collector drops out at the next scrape."""
    reg = MetricsRegistry()

    class Owner:
        def __init__(self, v):
            self.v = v

        def collect(self):
            yield Sample("pool_pages", "gauge", (("state", "free"),),
                         float(self.v), "h")

    a, b = Owner(3), Owner(4)
    reg.register_collector(a.collect)
    reg.register_collector(b.collect)
    assert "pool_pages{state=\"free\"} 7" in reg.render_prometheus()
    del b
    assert "pool_pages{state=\"free\"} 3" in reg.render_prometheus()


_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'  # escaped \" \\ \n ok
_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                    # metric name
    rf"(\{{{_LABEL}(,{_LABEL})*\}})?"               # label set
    r" (-?[0-9.e+-]+|\+Inf|-Inf|NaN)$")             # value


def _assert_prometheus_valid(text):
    """Golden-format check: every line is a comment or a valid sample;
    every sample's family has HELP+TYPE; histograms are cumulative with
    a +Inf bucket and _sum/_count."""
    typed, helped = {}, set()
    samples = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            helped.add(line.split()[2])
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram"), line
            typed[name] = kind
            continue
        assert _LINE.match(line), f"bad exposition line: {line!r}"
        samples.append(line)
    hist = {n for n, k in typed.items() if k == "histogram"}
    for line in samples:
        name = re.split(r"[{ ]", line, 1)[0]
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in hist:
                base = name[:-len(suffix)]
        assert base in typed and base in helped, f"untyped series {name}"
    return typed


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labels=("event",))
    c.labels(event="ok").inc(3)
    c.labels(event='we"ird\nname').inc()         # label escaping
    g = reg.gauge("depth", "queue depth")
    g.set(2.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    typed = _assert_prometheus_valid(text)
    assert typed == {"req_total": "counter", "depth": "gauge",
                     "lat_seconds": "histogram"}
    assert 'req_total{event="ok"} 3' in text
    assert r'we\"ird\nname' in text
    # histogram: cumulative buckets, +Inf == count, sum is the total
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert "lat_seconds_sum 5.55" in text


def test_gauge_function_and_snapshot_json():
    reg = MetricsRegistry()
    reg.gauge("lazy", "sampled at scrape").set_function(lambda: 41 + 1)
    reg.histogram("h_seconds", "h").observe(0.2)
    snap = reg.snapshot()
    json.dumps(snap)                        # JSON-able, incl. bucket keys
    by_name = {m["name"]: m for m in snap["metrics"]}
    assert by_name["lazy"]["samples"][0]["value"] == 42
    assert by_name["h_seconds"]["samples"][0]["count"] == 1
    assert "+Inf" in by_name["h_seconds"]["samples"][0]["buckets"]


def test_histogram_percentile_interpolation():
    reg = MetricsRegistry()
    h = reg.histogram("p_seconds", "p", buckets=(0.1, 1.0, 10.0))
    for _ in range(90):
        h.observe(0.05)
    for _ in range(10):
        h.observe(5.0)
    assert h.percentile(50) <= 0.1
    assert 1.0 <= h.percentile(99) <= 10.0
    assert reg.histogram("empty_seconds", "e").percentile(50) is None


# -- tracer ------------------------------------------------------------------

def test_tracer_ring_bound_and_chrome_schema():
    tr = Tracer(capacity=16)
    for i in range(20):
        with tr.span("work", cat="test", i=i):
            pass
    evs = tr.events()
    assert len(evs) == 16 and tr.dropped == 4
    assert evs[0]["args"]["i"] == 4              # oldest dropped first
    ids = [e["id"] for e in evs]
    assert ids == sorted(ids)                    # seeded, monotonic ids
    trace = tr.chrome_trace()
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    for e in trace["traceEvents"]:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0


def test_tracer_disable_is_noop_and_export(tmp_path):
    tr = Tracer()
    tr.disable()
    with tr.span("skipped"):
        pass
    tr.instant("skipped2")
    assert tr.events() == []
    tr.enable()
    tr.instant("kept")
    path = tmp_path / "trace.json"
    assert tr.export(str(path)) == 1
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"][0]["name"] == "kept"


def test_profiler_record_event_threadsafe_and_traced():
    """Satellite: concurrent record_event loses no events, and the same
    events land in the tracer (table and trace agree on counts)."""
    from paddle_tpu.fluid import profiler

    tr = tracer()
    tr.clear()
    profiler.reset_profiler()
    n_threads, k = 6, 200
    with profiler.profiler(print_table=False):
        def work():
            for _ in range(k):
                with profiler.record_event("conc"):
                    pass

        threads = [threading.Thread(target=work)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rows = {r["name"]: r for r in profiler.get_profile_table()}
    assert rows["conc"]["calls"] == n_threads * k
    assert len(tr.events("conc")) == n_threads * k


# -- seeded scheduler timeline ------------------------------------------------

def test_scheduler_span_timeline_reconstructs_lifecycle():
    """The acceptance timeline: a seeded run's trace contains, per
    request, submitted <= admitted <= token* <= retired with token
    instants exactly equal to the emitted tokens, and the whole-request
    X span matching the Request's own timestamps."""
    tr = tracer()
    tr.clear()
    rng = np.random.RandomState(0)
    sched = ContinuousBatchingScheduler(FakeModel(), n_slots=2,
                                        max_new_tokens=6)
    reqs = [sched.submit(rng.randint(2, 9, rng.randint(1, 5)),
                         max_new_tokens=int(rng.randint(2, 6)))
            for _ in range(5)]
    sched.run_until_idle()
    assert all(r.done and r.error is None for r in reqs)

    def by_rid(name):
        out = {}
        for e in tr.events(name):
            out.setdefault(e["args"]["rid"], []).append(e)
        return out

    subs, adms, toks, rets = (by_rid(n) for n in (
        "request/submitted", "request/admitted", "request/token",
        "request/retired"))
    spans = by_rid("request")
    for r in reqs:
        assert len(subs[r.rid]) == len(adms[r.rid]) == 1
        assert len(rets[r.rid]) == 1
        # token instants == emitted tokens, indices 1..n in order
        assert [e["args"]["index"] for e in toks[r.rid]] == \
            list(range(1, len(r.tokens) + 1))
        # ordering along the ring's timestamps
        assert subs[r.rid][0]["ts"] <= adms[r.rid][0]["ts"]
        assert adms[r.rid][0]["ts"] <= toks[r.rid][0]["ts"]
        assert toks[r.rid][-1]["ts"] <= rets[r.rid][0]["ts"] + 1e-3
        assert rets[r.rid][0]["args"]["tokens"] == len(r.tokens)
        # the whole-request span is stamped from the Request's marks
        (sp,) = spans[r.rid]
        assert sp["ph"] == "X"
        assert sp["ts"] == pytest.approx(r.submitted * 1e6)
        assert sp["dur"] == pytest.approx(
            (r.finished - r.submitted) * 1e6)
        # and the Request's own clock ordering holds
        assert r.submitted <= r.admitted <= r.first_token <= r.finished
    # one scheduler/step span per lockstep step
    assert len(tr.events("scheduler/step")) == sched.stats()["steps"]


def test_scheduler_stats_percentiles_satellite():
    sched = ContinuousBatchingScheduler(FakeModel(), n_slots=2,
                                        max_new_tokens=4)
    for _ in range(4):
        sched.submit([2, 3])
    sched.run_until_idle()
    st = sched.stats()
    # existing keys untouched (PR 5/6 contract)...
    for k in ("steps", "finished", "p50_latency_s", "p95_latency_s",
              "decoded_tok_per_s"):
        assert k in st
    # ...new percentile keys ride along
    assert st["p99_latency_s"] >= st["p95_latency_s"] >= 0
    assert 0 <= st["ttft_p50_s"] <= st["ttft_p95_s"]
    assert st["ttft_p95_s"] <= st["p95_latency_s"] + 1e-9
    assert st["tokens_per_request"] == {"p50": 4.0, "p95": 4.0, "max": 4}


def test_paged_prefill_chunk_spans():
    """The prefill leg of the timeline: a chunked-prefill admission
    emits one lane/prefill_chunk instant per dispatched chunk, covering
    the prompt exactly."""
    from paddle_tpu.serving import PagedTransformerGenerator

    tr = tracer()
    gen = PagedTransformerGenerator(
        24, 24, n_layer=2, n_head=2, d_key=4, d_value=4, d_model=16,
        d_inner_hid=32, max_length=64, src_len=8, max_out_len=8,
        page_size=4, chunk_size=4, num_pages=32, param_prefix="tfobs",
        place=fluid.CPUPlace())
    gen.init_params(seed=3)
    gen.open_slots(1)
    s_true = 7                                   # 2 chunks: 4 + 3
    gen.admit_slot(0, np.arange(2, 2 + s_true), max_new=4)
    tr.clear()
    steps = 0
    while gen._lanes[0].phase == "prefill":
        gen.lane_step()
        steps += 1
    chunks = [e["args"] for e in tr.events("lane/prefill_chunk")]
    assert len(chunks) == 2 == steps
    assert [c["tokens"] for c in chunks] == [4, 3]
    assert chunks[-1]["done"] == s_true == chunks[-1]["total"]
    gen.clear_slot(0)


# -- endpoints ----------------------------------------------------------------

def _get(addr, route):
    with urllib.request.urlopen(f"http://{addr}{route}", timeout=10) as r:
        return r.read()


def test_endpoints_roundtrip_live_scrape_during_run():
    """The acceptance scrape: /metrics during a serving run exposes
    labeled queue-depth, slot/page-utilization, TTFT, and guardrail
    counters in valid Prometheus text; /healthz and /statusz answer."""
    exe = fluid.Executor(fluid.CPUPlace())          # guardrail collector
    pool = PageAllocator(num_pages=16, page_size=4)  # page collector
    pool.alloc(3)
    sched = ContinuousBatchingScheduler(FakeModel(), n_slots=2,
                                        max_new_tokens=64)
    srv = ObservabilityServer()
    srv.attach("scheduler", sched).attach("executor", exe)
    srv.attach("callable", lambda: {"custom": 1})
    addr = srv.start()
    try:
        sched.serve()
        try:
            reqs = [sched.submit([2, 3, 4]) for _ in range(8)]
            # live mid-run scrape (requests decode 64 tokens each, so
            # the run comfortably outlasts the scrape)
            text = _get(addr, "/metrics").decode()
            for r in reqs:
                assert r.wait(timeout=60)
        finally:
            sched.shutdown()
        typed = _assert_prometheus_valid(text)
        assert typed["paddle_serving_queue_depth"] == "gauge"
        assert typed["paddle_serving_slot_utilization"] == "gauge"
        assert typed["paddle_kv_page_utilization"] == "gauge"
        assert typed["paddle_serving_ttft_seconds"] == "histogram"
        assert typed["paddle_guardrail_events_total"] == "counter"
        assert 'paddle_kv_pages{state="in_use"}' in text
        assert 'paddle_serving_requests_total{event="submitted"}' in text
        # per-shard pool residency (ISSUE 17): one labeled sample per
        # mesh model-axis shard of every live model
        assert typed["paddle_serving_shard_pool_bytes"] == "gauge"
        for shard in ("0", "1"):
            assert re.search(
                r'^paddle_serving_shard_pool_bytes\{model="default",'
                rf'shard="{shard}"\}} 4096', text, re.M), text

        health = json.loads(_get(addr, "/healthz"))
        assert health["ok"] is True and health["uptime_s"] >= 0

        status = json.loads(_get(addr, "/statusz"))
        assert set(status["sources"]) == {"callable", "executor",
                                          "scheduler"}
        assert status["callable"] == {"custom": 1}
        # a single-stats-method source attaches flat (scheduler.stats);
        # multi-method sources (the executor) nest under the method name
        assert status["scheduler"]["finished"] == 8
        assert "executable" in status["executor"]["cache_stats"]
        assert "skips" in status["executor"]["health_stats"]

        trace = json.loads(_get(addr, "/trace"))
        assert any(e["name"] == "request/retired"
                   for e in trace["traceEvents"])

        # unknown route -> structured 404
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(addr, "/nope")
        assert err.value.code == 404
    finally:
        srv.stop()


def test_statusz_broken_source_is_isolated():
    srv = ObservabilityServer()
    srv.attach("bad", lambda: 1 / 0)
    srv.attach("good", lambda: {"v": 2})
    addr = srv.start()
    try:
        status = json.loads(_get(addr, "/statusz"))
        assert status["good"] == {"v": 2}
        assert "ZeroDivisionError" in status["bad"]["error"]
    finally:
        srv.stop()


def test_attach_rejects_unusable_source():
    srv = ObservabilityServer()
    try:
        with pytest.raises(TypeError):
            srv.attach("nope", object())
    finally:
        # stop() without start() must release the socket, not deadlock
        # on shutdown()'s serve_forever handshake
        srv.stop()


def test_slot_utilization_aggregates_not_sums():
    """Two live schedulers at full occupancy must report utilization
    <= 1.0 (aggregate ratio over summed counts, the paging.py rule) —
    a per-instance ratio collector would sum to 2.0."""
    scheds = [ContinuousBatchingScheduler(FakeModel(), n_slots=1,
                                          max_new_tokens=4)
              for _ in range(2)]
    for s in scheds:
        s.submit([2, 3])
        s._admit_pending()              # occupy the lane, don't decode
    text = registry().render_prometheus()
    m = re.search(r"^paddle_serving_slot_utilization (\S+)$", text,
                  re.M)
    assert m and 0.0 < float(m.group(1)) <= 1.0, m
    for s in scheds:
        s.run_until_idle()


def test_server_start_after_stop_raises():
    srv = ObservabilityServer()
    srv.start()
    srv.stop()
    with pytest.raises(RuntimeError, match="after stop"):
        srv.start()


def test_histogram_bucket_conflict_raises():
    reg = MetricsRegistry()
    reg.histogram("hb_seconds", "h", buckets=(1, 2))
    reg.histogram("hb_seconds", "h", buckets=(1, 2))      # same: fine
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("hb_seconds", "h", buckets=(5, 6))


def test_nan_gauge_renders_instead_of_breaking_scrape():
    """A broken set_function gauge reports NaN and the scrape survives
    — one bad lazy gauge must not 500 every series."""
    reg = MetricsRegistry()
    reg.gauge("broken", "raises at scrape").set_function(
        lambda: 1 / 0)
    reg.gauge("fine", "ok").set(3)
    text = reg.render_prometheus()
    _assert_prometheus_valid(text)
    assert "broken NaN" in text
    assert "fine 3" in text


def test_labels_mismatch_raises_valueerror_not_keyerror():
    reg = MetricsRegistry()
    c = reg.counter("lbl_total", "l", labels=("event",))
    with pytest.raises(ValueError, match="missing \\['event'\\]"):
        c.labels()                       # declared label omitted
    with pytest.raises(ValueError, match="extra \\['evnt'\\]"):
        c.labels(evnt="typo")            # misnamed label, right count


def test_submitted_instant_precedes_queue_visibility():
    """The submitted mark is emitted BEFORE the request becomes
    admittable, so a threaded serve() can never trace admitted ahead of
    submitted (reviewed race)."""
    tr = tracer()
    tr.clear()
    sched = ContinuousBatchingScheduler(FakeModel(), n_slots=1,
                                        max_new_tokens=2)
    sched.serve()
    try:
        reqs = [sched.submit([2, 3]) for _ in range(6)]
        for r in reqs:
            assert r.wait(timeout=60)
    finally:
        sched.shutdown()
    subs = {e["args"]["rid"]: e["ts"]
            for e in tr.events("request/submitted")}
    for e in tr.events("request/admitted"):
        assert subs[e["args"]["rid"]] <= e["ts"]


def test_master_server_metrics_and_statusz_attach():
    from paddle_tpu.parallel.master import TaskQueue
    from paddle_tpu.parallel.master_service import MasterServer

    q = TaskQueue()
    q.set_dataset(["a", "b", "c"])
    master = MasterServer(q)
    master.start()
    try:
        text = registry().render_prometheus()
        assert 'paddle_master_tasks{state="todo"}' in text
        srv = ObservabilityServer()
        srv.attach("master", master)
        addr = srv.start()
        try:
            status = json.loads(_get(addr, "/statusz"))
            assert status["master"]["todo"] == 3
        finally:
            srv.stop()
    finally:
        master.stop()


def test_obs_cli_roundtrip(tmp_path, capsys):
    from paddle_tpu.tools import obs

    tr = tracer()
    tr.instant("cli/mark")
    srv = ObservabilityServer()
    srv.attach("demo", lambda: {"x": 1})
    addr = srv.start()
    try:
        assert obs.main(["healthz", addr]) == 0
        assert '"ok": true' in capsys.readouterr().out

        assert obs.main(["metrics", addr,
                         "--grep", "paddle_serving"]) == 0
        out = capsys.readouterr().out
        assert all("paddle_serving" in ln
                   for ln in out.splitlines() if ln.strip())

        assert obs.main(["statusz", addr]) == 0
        assert json.loads(capsys.readouterr().out)["demo"] == {"x": 1}

        dump = tmp_path / "t.json"
        assert obs.main(["trace", addr, "-o", str(dump)]) == 0
        names = [e["name"]
                 for e in json.loads(dump.read_text())["traceEvents"]]
        assert "cli/mark" in names
    finally:
        srv.stop()
    # unreachable endpoint -> exit 2
    assert obs.main(["healthz", "127.0.0.1:1", "--timeout", "0.2"]) == 2


def test_guardrail_counters_exported_on_recovery():
    """A skipped non-finite step shows up both in health_stats() (the
    dict view) and the exported guardrail series + guard/skip trace
    instant — one signal, three faces."""
    from paddle_tpu.resilience import GuardPolicy

    tr = tracer()
    tr.clear()
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [2], "float32")
        y = fluid.layers.mean(fluid.layers.fc(input=x, size=2))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {"x": np.array([[np.nan, 1.0]], np.float32)}
        exe.run(main, feed=feed, fetch_list=[y],
                guard=GuardPolicy(on_nonfinite="skip", check=("loss",)))
    assert exe.health_stats()["skips"] == 1
    text = registry().render_prometheus()
    m = re.search(
        r'paddle_guardrail_events_total\{event="skips"\} (\d+)', text)
    assert m and int(m.group(1)) >= 1
    assert len(tr.events("guard/skip")) == 1


# -- one call site, two sinks; a cause on every span (ISSUE 25) ---------------

def test_nested_spans_carry_parent_and_hand_down_rid_and_step():
    tr = Tracer()
    with tr.span("outer", step=7) as filled:
        tr.instant("mark", rid=3)
        with tr.span("inner"):
            with tr.span("innermost", step=8):
                tr.instant("deep")
        filled["rid"] = 11                      # known only at the end
    with tr.span("alone"):
        pass
    by = {e["name"]: e for e in tr.events()}
    assert "parent" not in by["outer"] and "parent" not in by["alone"]
    assert by["mark"]["parent"] == by["outer"]["id"]
    assert by["inner"]["parent"] == by["outer"]["id"]
    assert by["innermost"]["parent"] == by["inner"]["id"]
    assert by["deep"]["parent"] == by["innermost"]["id"]
    # step is handed down until a span sets its own; rid set late is the
    # span's own and nobody else's
    assert by["mark"]["args"] == {"rid": 3, "step": 7}
    assert by["inner"]["args"] == {"step": 7}
    assert by["deep"]["args"] == {"step": 8}
    assert by["outer"]["args"] == {"step": 7, "rid": 11}
    assert "args" not in by["alone"]
    # a span takes its id when it opens: ids follow the starts
    assert by["outer"]["id"] < by["inner"]["id"] < by["innermost"]["id"]
    # another thread's spans are no parent of this thread's
    seen = []

    def other():
        with tr.span("elsewhere"):
            pass
        seen.extend(tr.events("elsewhere"))

    with tr.span("here"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert "parent" not in seen[0]


@pytest.fixture(scope="module")
def tiny_paged():
    from paddle_tpu.serving import PagedTransformerGenerator

    gen = PagedTransformerGenerator(
        24, 24, n_layer=2, n_head=2, d_key=4, d_value=4, d_model=16,
        d_inner_hid=32, max_length=64, src_len=8, max_out_len=8,
        page_size=4, chunk_size=4, num_pages=32, param_prefix="tfspan",
        end_id=10 ** 6, place=fluid.CPUPlace())
    gen.init_params(seed=5)
    return gen


def _serve_one(gen, max_new=3):
    """One streamed request through the gateway's front door; returns its
    rid once every token has been consumed."""
    from paddle_tpu.serving.gateway import Gateway

    gw = Gateway(n_slots=2, max_new_tokens=8)
    gw.load_model("spans", "1", instance=gen, n_slots=2)
    try:
        stream = gw.submit_stream("spans", np.arange(2, 8), max_new=max_new)
        gw.run_until_idle()
        assert len(list(stream)) == max_new
        return stream.request.rid
    finally:
        gw.unload_model("spans")


def test_one_request_through_the_gateway_is_one_rid_in_order(tiny_paged):
    tr = tracer()
    tr.clear()
    rid = _serve_one(tiny_paged)
    mine = [e for e in tr.events()
            if (e.get("args") or {}).get("rid") == rid]
    first = {}
    for e in sorted(mine, key=lambda e: e["ts"]):
        first.setdefault(e["name"], e)
    order = ["gateway/ingress", "request/submitted", "request/admitted",
             "lane/prefill_chunk", "request/token", "gateway/first_chunk"]
    assert [n for n in first if n in order] == order
    # once per request, nothing per token
    assert len(tr.events("gateway/ingress")) == 1
    assert len(tr.events("gateway/first_chunk")) == 1
    # the scheduler's instant was emitted under the gateway's span
    assert first["request/submitted"]["parent"] == \
        first["gateway/ingress"]["id"]


def test_serve_step_spans_share_step_and_only_nest(tiny_paged):
    tr = tracer()
    tr.clear()
    _serve_one(tiny_paged, max_new=4)
    spans = [e for e in tr.events() if e["ph"] == "X" and e["name"].startswith(
        ("scheduler/", "engine/", "executor"))]
    by_step = {}
    for e in spans:                 # load and warm-up run outside any step
        if "step" in (e.get("args") or {}):
            by_step.setdefault(e["args"]["step"], []).append(e)
    stepped = {s: evs for s, evs in by_step.items()
               if any(e["name"] == "scheduler/step" for e in evs)}
    assert len(stepped) >= 4                    # 2 prefill chunks + decode
    for step, evs in stepped.items():
        names = [e["name"] for e in evs]
        for want in ("scheduler/round", "scheduler/admit", "scheduler/plan",
                     "scheduler/step", "scheduler/deliver",
                     "scheduler/maintenance", "engine/feed_build", "engine/dispatch", "engine/fetch",
                     "engine/absorb", "executor/prepare",
                     "executor_step/infer", "executor/writeback",
                     "executor/release"):
            assert names.count(want) == 1, (step, want, names)
        assert "executor/compile" not in names or step == min(stepped)
        ids = {e["id"]: e for e in evs}
        for e in evs:
            for o in evs:
                if o is e or o["ts"] < e["ts"]:
                    continue
                a0, a1 = e["ts"], e["ts"] + e["dur"]
                b0, b1 = o["ts"], o["ts"] + o["dur"]
                # o starts inside e: then it ends inside e, and says so
                if b0 < a1:
                    assert b1 <= a1 + 1e-3, (e["name"], o["name"])
                    up = o
                    while up.get("parent") in ids and up is not e:
                        up = ids[up["parent"]]
                    assert up is e, (e["name"], o["name"])
        under = {e["name"]: ids[e["parent"]]["name"] for e in evs
                 if e.get("parent") in ids}
        assert "scheduler/round" not in under      # the step's top span
        for phase in ("admit", "plan", "step", "deliver", "maintenance"):
            assert under["scheduler/" + phase] == "scheduler/round"
        assert under["engine/feed_build"] == "scheduler/step"
        assert under["executor/prepare"] == "engine/dispatch"
        assert under["executor_step/infer"] == "engine/dispatch"


def _host_span_names(trace_dir):
    import glob

    import jax

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return {e.name for p in data.planes if p.name.startswith("/host:CPU")
            for ln in p.lines for e in ln.events}


def _profiled(trace_dir, body):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_span_names(trace_dir)


def test_profiler_session_holds_the_programs_spans(tiny_paged, tmp_path):
    """The second sink: the same ``with`` puts the span on the host plane
    of an open ``jax.profiler`` session, under its own name."""
    names = _profiled(tmp_path, lambda: _serve_one(tiny_paged))
    assert {"gateway/ingress", "scheduler/step", "scheduler/deliver",
            "engine/feed_build", "engine/fetch", "executor/prepare",
            "executor_step/infer", "executor/writeback"} <= names
    # instants and retrospective events stay in the ring alone
    assert not {"request/token", "request"} & names


@pytest.mark.parametrize("enabled, session", [(True, False), (False, False),
                                              (False, True)])
def test_span_body_runs_whatever_the_sinks(tmp_path, enabled, session):
    tr = Tracer(enabled=enabled)
    ran = []

    def body():
        with tr.span("sink-check", n=1) as filled:
            filled["more"] = 2
            ran.append(True)

    names = _profiled(tmp_path, body) if session else (body() or set())
    assert ran == [True]
    if enabled:
        (ev,) = tr.events()
        assert ev["name"] == "sink-check" and ev["args"] == {"n": 1,
                                                            "more": 2}
    else:                                       # disable(): both sinks off
        assert tr.events() == [] and "sink-check" not in names


def test_ring_and_harness_clocks_are_one():
    """The ring stamps ``perf_counter``, the benchmark's windows
    ``monotonic``: the per-layer readers compare them directly."""
    import time

    gaps = []
    for _ in range(5):
        a = time.monotonic()
        b = time.perf_counter()
        c = time.monotonic()
        gaps.append(abs(b - 0.5 * (a + c)))
    assert min(gaps) < 1e-3


# -- the books of a serve round: thread time on every span, one span over
# -- the round (ISSUE 40) ------------------------------------------------------

def _spin_cpu(seconds):
    """Burn ``seconds`` of THIS thread's CPU time, however long that takes
    on a busy host."""
    import time

    until = time.thread_time() + seconds
    while time.thread_time() < until:
        pass


def test_a_sleeping_span_reads_little_thread_time_a_spinning_one_its_own():
    import time

    tr = Tracer()
    with tr.span("asleep"):
        time.sleep(0.05)
    with tr.span("spinning"):
        _spin_cpu(0.03)
    with tr.span("empty"):
        pass
    tr.instant("mark")
    tr.complete("elsewhere", 1.0, 2.0)
    by = {e["name"]: e for e in tr.events()}
    assert by["asleep"]["dur"] >= 50e3
    assert by["asleep"]["tdur"] < 0.2 * by["asleep"]["dur"]
    assert by["spinning"]["tdur"] >= 29e3           # microseconds, as dur
    # the thread's clock is read inside the wall clock's two reads: never
    # over dur by more than the two clocks' own skew (a host whose kernel
    # counts thread time in ticks is another matter: PERF.md section 6)
    for name in ("asleep", "spinning", "empty"):
        assert 0.0 <= by[name]["tdur"] <= by[name]["dur"] + 1.0, name
    # an instant has no duration and a complete() no thread of its own
    assert "tdur" not in by["mark"] and "tdur" not in by["elsewhere"]
    # Chrome's trace format, as exported: tdur beside dur on the X event
    exported = {e["name"]: e for e in tr.chrome_trace()["traceEvents"]}
    assert exported["spinning"]["tdur"] == by["spinning"]["tdur"]


def test_a_disabled_tracer_emits_nothing_and_reads_no_clock(monkeypatch):
    import time

    tr = Tracer(enabled=False)
    monkeypatch.setattr(time, "thread_time", None)  # a call would raise
    with tr.span("off") as filled:
        filled["n"] = 1
    tr.instant("off")
    tr.complete("off", 1.0, 2.0)
    assert tr.events() == [] and tr.dropped == 0


def test_a_clock_that_ticks_is_not_read_faster_than_it_ticks(monkeypatch):
    """The chip's host: a sandboxed kernel counts a thread's time in ticks
    of 10 ms and a read is a system call.  The tracer finds the tick once
    and does not read the clock again within a twentieth of it; the spans
    still sum to the ticks the thread was charged."""
    import time

    from paddle_tpu.observability import tracing

    clock = {"wall": 100.0, "reads": 0}

    def ticking():                      # 70 % on the CPU, in whole ticks
        clock["reads"] += 1
        clock["wall"] += 6e-6           # a read is a system call
        return int(0.7 * clock["wall"] / 0.01) * 0.01

    def wall():
        clock["wall"] += 1e-6           # a microsecond a look
        return clock["wall"]

    tracing._thread_clock_tick.cache_clear()
    monkeypatch.setattr(time, "thread_time", ticking)
    monkeypatch.setattr(time, "perf_counter", wall)
    try:
        assert tracing._thread_clock_tick() == pytest.approx(0.01)
        tr = Tracer()
        before = clock["reads"]
        with tr.span("round"):
            for _ in range(20):         # a step of 10 us spans: one read
                with tr.span("leaf"):
                    clock["wall"] += 10e-6
        assert clock["reads"] - before == 1
        with tr.span("long"):           # past the twentieth: read again
            clock["wall"] += 0.1
        assert clock["reads"] - before == 2
        (long,) = tr.events("long")
        assert long["tdur"] == pytest.approx(70e3, abs=10e3)
        assert all(e["tdur"] == 0.0 for e in tr.events("leaf"))
    finally:
        monkeypatch.undo()
        tracing._thread_clock_tick.cache_clear()
    # this host's own clock runs fine: every boundary reads it
    assert Tracer()._cpu_reread_s < 1e-6


def test_off_cpu_time_shows_beside_a_busy_python_thread():
    """``dur - tdur`` tells waiting from working: the same pure-Python
    span waits for the interpreter beside a thread that wants it too, and
    does not alone.  Shares of the span, so a starved host moves both."""
    def off_cpu_share(busy):
        tr = Tracer()
        stop = threading.Event()

        def rival():
            while not stop.is_set():
                sum(range(200))

        other = threading.Thread(target=rival, daemon=True)
        if busy:
            other.start()
        try:
            with tr.span("work"):
                _spin_cpu(0.08)
        finally:
            stop.set()
            if busy:
                other.join()
        (ev,) = tr.events("work")
        return (ev["dur"] - ev["tdur"]) / ev["dur"]

    for _attempt in range(3):           # the host's own scheduler is noise
        alone, beside = off_cpu_share(False), off_cpu_share(True)
        if beside > 0.25 and beside > alone + 0.15:
            break
    assert beside > 0.25 and beside > alone + 0.15, (alone, beside)


class FakeLanes:
    """Self-managed fake: every admitted lane emits token 5 a step."""

    start_id, end_id = 0, 1

    def __init__(self):
        self.live = set()

    def open_slots(self, n):
        self.n = n

    def admit_slot(self, slot, prompt, **_):
        self.live.add(slot)
        return len(prompt)

    def clear_slot(self, slot):
        self.live.discard(slot)

    def lane_step(self):
        with tracer().span("engine/fake", cat="serving"):
            return {slot: 5 for slot in sorted(self.live)}


@pytest.fixture(scope="module")
def tiny_paged_lm():
    from paddle_tpu.serving import PagedLMGenerator
    from perfbench import weights
    from perfbench.families import mimo_v2_flash as fam

    with open("perfbench/configs/mimo-v2-flash-ep32.json",
              encoding="utf-8") as f:
        cfg = {**json.load(f), **fam.REHEARSAL["serve"]["cfg"]}
    conf = fam.serving(cfg)["manifest"]["config"]
    gen = PagedLMGenerator(**conf)
    gen.load_weights(weights.make(
        fam.param_shapes(cfg, cfg["param_prefix"]), 40, kind_of=fam.leaf_kind))
    return gen, conf["lanes"]


def _rounds_of(kind, request):
    """Drive a few requests through one scheduler; -> the ring's events."""
    tr = tracer()
    tr.clear()
    if kind in ("step_slots", "lane_step"):
        # the fakes under serve(): a loop thread beside a delivery thread
        model = FakeModel() if kind == "step_slots" else FakeLanes()
        sched = ContinuousBatchingScheduler(model, n_slots=2).serve()
        try:
            reqs = [sched.submit([2, 3], max_new_tokens=4) for _ in range(3)]
            assert all(r.wait(10) for r in reqs)
        finally:
            sched.shutdown(drain=True)
    elif kind == "paged_decoder":
        _serve_one(request.getfixturevalue("tiny_paged"), max_new=4)
    else:
        gen, lanes = request.getfixturevalue("tiny_paged_lm")
        sched = ContinuousBatchingScheduler(gen, n_slots=lanes)
        reqs = [sched.submit(np.arange(3, 3 + n), max_new_tokens=3)
                for n in (5, 9)]
        sched.run_until_idle()
        assert all(r.done and r.error is None for r in reqs)
    return tr.events()


@pytest.mark.parametrize("kind", ["step_slots", "lane_step", "paged_decoder",
                                  "paged_lm"])
def test_every_span_of_a_round_reaches_scheduler_round(kind, request):
    """Both stepping modes on fake lanes, both engines at a tiny size: in
    a dispatched round every span of the loop's thread lies under ONE
    ``scheduler/round``, carries its ``step``, and no span's children
    outlast it: the round's time is a sum with nothing outside."""
    evs = _rounds_of(kind, request)
    spans = {e["id"]: e for e in evs if e["ph"] == "X"}
    rounds = [e for e in spans.values() if e["name"] == "scheduler/round"]
    assert rounds and all("parent" not in r for r in rounds)
    dispatched = 0
    for r in rounds:
        inside = [e for e in spans.values() if e is not r
                  and e["tid"] == r["tid"]
                  and r["ts"] <= e["ts"] <= r["ts"] + r["dur"]]
        if not any(e["name"] == "scheduler/deliver" for e in inside):
            continue                    # found nothing to do: left out
        dispatched += 1
        covered = {}
        for e in inside:
            top = e
            while top.get("parent") in spans:
                top = spans[top["parent"]]
            assert top is r, (kind, e["name"], top["name"])
            assert e["args"]["step"] == r["args"]["step"], e["name"]
            assert e["tdur"] <= e["dur"] + 1.0
            covered[e["parent"]] = covered.get(e["parent"], 0.0) + e["dur"]
        for parent, total in covered.items():
            assert total <= spans[parent]["dur"] + 1e-3, spans[parent]["name"]
        names = {e["name"] for e in inside}
        assert {"scheduler/admit", "scheduler/plan", "scheduler/step",
                "scheduler/deliver"} <= names
        want = {"step_slots": set(), "lane_step": {"engine/fake"}}.get(
            kind, {"engine/feed_build", "engine/dispatch", "engine/fetch",
                   "engine/absorb", "executor/prepare",
                   "executor_step/infer", "executor/writeback",
                   "executor/release"})
        assert want <= names, (kind, names)
    assert dispatched >= 3
    # what runs between rounds stays outside them
    for e in spans.values():
        if e["name"] in ("scheduler/wait", "scheduler/deliver_out"):
            assert "parent" not in e, e["name"]
