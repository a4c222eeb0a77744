"""The load generator against a stand-in server: an open loop times each
request from when it was DUE and reports how late it was sent; a closed
loop sends a client's next request when the last completes; when the
window closes every connection is cut, and a request cut short is marked so
and is no failure, while one that has no first token by then misses."""

import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from perfbench import loadgen, serve_cell


class _Fake(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay = 0.01

    def log_message(self, *a):
        pass

    def do_POST(self):
        try:
            self.answer()
        except OSError:                 # the client was cut at the close
            self.close_connection = True

    def answer(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if body["prompt"][0] == 999:
            payload = b'{"error": "nope"}'
            self.send_response(413)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(data):
            self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()

        for i in range(body["max_new"]):
            time.sleep(self.delay * {777: 50, 666: 8}.get(body["prompt"][0], 1))
            chunk(json.dumps({"token": i + body["prompt"][0]}).encode() + b"\n")
        chunk(json.dumps({"done": True, "tokens": body["max_new"]}).encode()
              + b"\n")
        if body["prompt"][0] == 555:    # the cut falls before the last chunk
            time.sleep(0.5)
        chunk(b"")


@pytest.fixture
def addr():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Fake)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield "127.0.0.1:%d" % httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()
    t.join(timeout=5)
    assert not t.is_alive()


def plan(addr, loop, requests, horizon, **more):
    t0 = time.monotonic() + 0.1
    return dict({"addr": addr, "model": "m", "loop": loop, "t0": t0,
                 "stop_at": t0 + horizon, "grace_s": 1.0, "workers": 1,
                 "requests": requests}, **more)


def test_open_loop_times_from_the_due_time_and_reports_lateness(addr):
    # one worker, three requests due together: the later ones wait for the
    # worker, and that wait is in their time from due, not hidden
    reqs = [{"id": i, "prompt": [10 * i], "max_new": 5, "due_s": 0.05}
            for i in range(3)]
    p = plan(addr, "open", reqs, horizon=1.0)
    out = loadgen.run(p)
    recs = sorted(out["records"], key=lambda r: r["sent"])
    assert [r["status"] for r in recs] == [200] * 3
    assert all(r["due"] == pytest.approx(p["t0"] + 0.05) for r in recs)
    late = [r["sent"] - r["due"] for r in recs]
    assert late[0] >= 0 and late[1] >= 0.04 and late[2] >= 0.09
    ttft = [r["times"][0] - r["due"] for r in recs]
    assert ttft[2] > ttft[1] > ttft[0] >= 0.01
    assert recs[0]["tokens"] == [recs[0]["id"] * 10 + k for k in range(5)]
    assert all(len(r["times"]) == 5 and r["done"] >= r["times"][-1]
               for r in recs)
    nums = serve_cell.window_numbers(out["records"], reqs, True, p["t0"],
                                     p["t0"], p["stop_at"])
    assert nums["failed"] == 0 and nums["attempted"] == 3
    assert max(nums["late_ms"]) >= 90 and len(nums["ttft_ms"]) == 3
    assert nums["tokens_in_window"] == 15 and len(nums["gaps_ms"]) == 12


def test_closed_loop_sends_the_next_when_the_last_completes(addr):
    reqs = [{"id": i, "prompt": [i], "max_new": 3, "client": i % 2}
            for i in range(40)]
    out = loadgen.run(plan(addr, "closed", reqs, horizon=0.4))
    recs = out["records"]
    assert 4 <= len(recs) < 40           # stopped by the clock, not the list
    for client in (0, 1):
        mine = sorted((r for r in recs if r["id"] % 2 == client),
                      key=lambda r: r["sent"])
        assert [r["id"] for r in mine] == [client + 2 * k
                                          for k in range(len(mine))]
        for a, b in zip(mine, mine[1:]):
            assert b["sent"] >= a["done"]


def test_refusals_fail_and_the_close_cuts_without_failing(addr):
    reqs = [{"id": 0, "prompt": [999], "max_new": 2, "due_s": 0.0},
            {"id": 1, "prompt": [666], "max_new": 50, "due_s": 0.0},
            {"id": 2, "prompt": [5], "max_new": 2, "due_s": 0.01},
            {"id": 3, "prompt": [777], "max_new": 2, "due_s": 0.25}]
    p = plan(addr, "open", reqs, horizon=0.3, workers=4, grace_s=0.2)
    t_start = time.monotonic()
    out = loadgen.run(p)
    # cut at the close plus the grace: nothing is drained (request 1 alone
    # would take 4 s)
    assert time.monotonic() - t_start < 3.0 and out["threads_stuck"] == 0
    assert p["stop_at"] + 0.2 <= out["cut_at"] < p["stop_at"] + 0.5
    recs = {r["id"]: r for r in out["records"]}
    assert recs[0]["status"] == 413 and recs[0]["error"] and not recs[0]["cut"]
    assert recs[2]["error"] is None and len(recs[2]["tokens"]) == 2
    assert not recs[2]["cut"]
    # cut mid-stream: some tokens, marked, no failure
    assert recs[1]["cut"] and 0 < len(recs[1]["tokens"]) < 50
    # due inside the window, first token (0.5 s away) later than the grace
    assert recs[3]["cut"] and recs[3]["times"] == []
    nums = serve_cell.window_numbers(out["records"], reqs, True, p["t0"],
                                     p["t0"], p["stop_at"])
    assert nums["failed"] == 1 and nums["attempted"] == 4
    # a refusal, and a first token that never came, miss by the window
    assert sorted(nums["ttft_ms"])[2:] == [pytest.approx(300.0)] * 2
    assert len(nums["ttft_ms"]) == 4


def test_an_answer_complete_at_the_cut_is_no_failure(addr):
    """The done line has come and the cut breaks the read of the
    terminating chunk: the request stands as answered (on the chip one
    flood run in 17 failed on exactly this, PR 24)."""
    reqs = [{"id": 0, "prompt": [555], "max_new": 3, "client": 0}]
    p = plan(addr, "closed", reqs, horizon=0.25, grace_s=0.0)
    out = loadgen.run(p)
    (rec,) = out["records"]
    assert rec["done"] is not None and rec["done"] < out["cut_at"]
    assert not rec["cut"] and rec["error"] is None
    assert serve_cell.request_ok(rec, reqs[0])
    assert not serve_cell.request_failed(rec, reqs[0])


def test_the_close_waits_for_first_tokens_owed_but_no_longer(addr):
    reqs = [{"id": 0, "prompt": [5], "max_new": 200, "due_s": 0.28}]
    p = plan(addr, "open", reqs, horizon=0.3, grace_s=5.0)
    out = loadgen.run(p)
    (rec,) = out["records"]
    # its first token came ~10 ms after it was sent; the cut followed at
    # once, far inside the grace
    assert rec["cut"] and len(rec["times"]) >= 1
    assert out["cut_at"] < p["stop_at"] + 1.0


def test_a_closed_loop_is_cut_at_the_close(addr):
    reqs = [{"id": i, "prompt": [777], "max_new": 50, "client": i}
            for i in range(3)]
    p = plan(addr, "closed", reqs, horizon=0.3, grace_s=0.0)
    out = loadgen.run(p)
    assert out["cut_at"] < p["stop_at"] + 0.2
    assert len(out["records"]) == 3 and all(r["cut"] for r in out["records"])
    nums = serve_cell.window_numbers(out["records"], reqs, False, p["t0"],
                                     p["t0"], p["stop_at"])
    assert nums["failed"] == 0 and nums["attempted"] == 3
    assert nums["ttft_ms"] == []        # a closed loop's queue is no miss


def test_the_generator_never_imports_jax():
    import subprocess

    code = ("import sys, perfbench.loadgen; "
            "sys.exit(1 if 'jax' in sys.modules or 'paddle_tpu' in "
            "sys.modules or 'numpy' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0
