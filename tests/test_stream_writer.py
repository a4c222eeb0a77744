"""The single writer (ISSUE 39): a streaming response's chunks are written
to its socket by the scheduler's delivery thread (``TokenStream`` with a
sink attached), and the request's handler thread sleeps from the headers
to the response's end.  Here: the hand-over at each end of a response,
the way out for a reader that stops reading, what a failed send does, the
bytes on the wire, the door's counters, and the door itself.  On
``test_delivery.py``'s fake lanes: the gateway is what is under test.
"""

import http.client
import importlib.util
import itertools
import json
import os
import socket
import sys
import threading
import time

import pytest
from test_delivery import GatedLanes, LaneModel, limited, wait_for

from paddle_tpu.observability import tracer
from paddle_tpu.serving import scheduler as scheduler_mod
from paddle_tpu.serving.gateway import Gateway, GatewayServer, TokenStream
from paddle_tpu.serving.gateway import server as server_mod
from paddle_tpu.serving.scheduler import Request, RequestCancelled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def door(model, lanes=4, most=64, timeout=15.0):
    gw = Gateway(n_slots=lanes, max_new_tokens=most)
    gw.load_model("m", "1", instance=model, warm=False)
    srv = GatewayServer(gw, request_timeout=timeout)
    host, port = srv.start().split(":")
    return gw, srv, host, int(port)


class PagedLanes(LaneModel):
    """Page-aware as the scheduler sees it: a lane holds pages from its
    admission until ``clear_slot`` gives them back."""

    page_aware = True

    def __init__(self, **kw):
        super().__init__(**kw)
        self.pages = {}

    def prompt_infeasible(self, src, max_new):
        return False

    def can_admit(self, src, max_new):
        return True

    def admit_slot(self, slot, prompt, **kw):
        self.pages[slot] = 4
        return super().admit_slot(slot, prompt, **kw)

    def clear_slot(self, slot):
        super().clear_slot(slot)
        self.pages.pop(slot, None)


def settled(gw, **want):
    """The door's counters, once they read ``want``: a sink counts an
    item after its send returned, so a client may have the response's
    last byte a moment before the count shows it."""
    wait_for(lambda: all(gw.streams.snapshot()[k] == v
                         for k, v in want.items()))
    return gw.streams.snapshot()


def post(sock, body):
    payload = json.dumps(body).encode()
    sock.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                 b"Content-Type: application/json\r\n"
                 b"Content-Length: %d\r\n\r\n" % len(payload) + payload)


def read_response(sock, got=b""):
    """Everything up to the terminating chunk, as it came."""
    while not got.endswith(b"0\r\n\r\n"):
        data = sock.recv(65536)
        assert data, ("the connection closed inside a response", got[-80:])
        got += data
    return got


def lines_of(raw):
    """The JSON lines of a chunked response's body."""
    body = raw.split(b"\r\n\r\n", 1)[1]
    return [json.loads(ln) for ln in body.split(b"\r\n")
            if ln.startswith(b"{")]


def stream_request(host, port, body):
    conn = http.client.HTTPConnection(host, port, timeout=15)
    try:
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, [json.loads(ln) for ln in
                             resp.read().decode().splitlines()]
    finally:
        conn.close()


# -- the stream and a sink, no HTTP --------------------------------------------

class Sink:
    """Takes items whole until ``stop_at`` of them are written; then what
    ``how`` says: keeps the item (``False``) or raises."""

    def __init__(self, stop_at=None, how="blocks"):
        self.got, self.stop_at, self.how = [], stop_at, how

    def write(self, tok, direct):
        if self.stop_at is not None and len(self.got) >= self.stop_at:
            if self.how == "raises":
                raise BrokenPipeError("nobody reads")
            return False
        self.got.append((tok, direct))
        return True


def pushed_stream(timeout=5.0):
    stream = TokenStream(timeout=timeout)
    stream.request = Request([2], 16, on_token=stream._push)
    return stream


@pytest.mark.parametrize("queued", [0, 3, 7], ids=["none", "some", "all"])
def test_attach_drains_the_queue_once_and_in_order(queued):
    stream, sink = pushed_stream(), Sink()
    items = list(range(50, 56)) + [None]
    for tok in items[:queued]:
        stream._push(stream.request, tok)
    stream.attach(sink)
    for tok in items[queued:]:
        stream._push(stream.request, tok)
    # what was queued before the attach is written by the attaching
    # thread, what came after by the pusher; each once, in order
    assert sink.got == [(tok, i >= queued) for i, tok in enumerate(items)]
    assert stream._released.is_set() and stream._sink is None
    assert stream._q.empty() and not stream.failed
    stream.park()                               # returns at once
    stream.close()
    assert not stream.request.cancelled         # ended: nothing to cancel


@pytest.mark.parametrize("queued", [0, 5], ids=["pushed", "draining"])
def test_a_sink_that_cannot_take_an_item_hands_the_stream_back(queued):
    stream, sink = pushed_stream(), Sink(stop_at=3)
    for tok in range(50, 50 + queued):
        stream._push(stream.request, tok)
    stream.attach(sink)
    for tok in range(50 + queued, 58):
        stream._push(stream.request, tok)
    stream._push(stream.request, None)
    assert [tok for tok, _ in sink.got] == [50, 51, 52]
    assert stream._released.is_set() and not stream.failed
    # the item the sink kept (53) is the sink's to finish; every later
    # one is the iterator's, in order, to the end
    stream.request._done.set()
    assert list(stream) == [54, 55, 56, 57]
    assert not stream.request.cancelled


def test_a_sink_that_raises_cancels_the_request_and_drops_the_rest():
    stream, sink = pushed_stream(), Sink(stop_at=2, how="raises")
    stream.attach(sink)
    for tok in (50, 51, 52, 53):
        stream._push(stream.request, tok)
    assert [tok for tok, _ in sink.got] == [50, 51]
    assert stream.failed and stream._released.is_set()
    assert stream.request.cancelled and stream._q.empty()
    stream._push(stream.request, None)          # the cancelled request's
    assert stream._q.empty()                    # end: nobody is told


@limited(30)
def test_attach_racing_the_pusher_loses_and_doubles_nothing():
    """The hand-over at the start, as a race: one thread pushes while
    another attaches, with the interpreter switching every 10 us.  Every
    item reaches the sink once and in order, whichever side wrote it."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for n in range(300):
            stream, sink = pushed_stream(), Sink()
            items = list(range(40)) + [None]
            go = threading.Barrier(2)

            def push():
                go.wait(5)
                for tok in items:
                    stream._push(stream.request, tok)

            pusher = threading.Thread(target=push, daemon=True)
            pusher.start()
            go.wait(5)
            for _ in range(n % 7):              # attach a little later
                time.sleep(0)
            stream.attach(sink)
            pusher.join(5)
            assert not pusher.is_alive()
            assert [tok for tok, _ in sink.got] == items, (n, sink.got)
            by_pusher = [direct for _, direct in sink.got]
            assert by_pusher == sorted(by_pusher)   # the drain comes first
            assert stream._q.empty() and stream._released.is_set()
    finally:
        sys.setswitchinterval(was)


@limited(10)
def test_park_gives_a_stream_that_is_pushed_nothing_the_iterators_error():
    stream, sink = pushed_stream(timeout=0.1), Sink()
    stream.attach(sink)
    threading.Timer(0.05, stream._push, (stream.request, 50)).start()
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="no token for 0.1s"):
        stream.park()
    # one quiet ``timeout`` after the last push, at most two
    assert 0.15 <= time.monotonic() - t0 < 1.0
    assert stream._sink is None and sink.got == [(50, True)]
    stream._push(stream.request, 51)            # the consumer's again
    assert stream._q.get_nowait() == 51


def test_first_chunk_is_noted_by_whoever_sent_the_first_tokens_chunk():
    ring = tracer()
    ring.clear()
    stream, sink = pushed_stream(), Sink()
    stream.attach(sink)
    for tok in (50, 51, None):
        stream._push(stream.request, tok)
    kept, back = pushed_stream(), Sink(stop_at=0)
    kept.attach(back)
    for tok in (60, 61, None):
        kept._push(kept.request, tok)
    kept.request._done.set()
    assert list(kept) == [61]                   # 60 is the sink's
    marks = ring.events(name="gateway/first_chunk")
    # once each: by the sink's caller after the first token's write, by
    # the iterator (the consumer is back, so it dealt with what it got)
    assert [e["args"]["rid"] for e in marks] == [stream.request.rid,
                                                 kept.request.rid]


# -- the hand-over at the start, through the door ------------------------------

@pytest.mark.parametrize("held_for", ["some", "all"])
@limited(15)
def test_tokens_queued_before_the_attach_arrive_once_and_in_order(
        monkeypatch, held_for):
    """The handler is held between ``submit_stream`` and the attach while
    the lanes decode: those tokens wait in the stream's queue, and the
    attach writes them ahead of everything the delivery thread sends."""
    gate, streams = threading.Event(), []
    real_attach = TokenStream.attach

    def attach(self, sink):
        streams.append(self)
        gate.wait(10)
        real_attach(self, sink)

    monkeypatch.setattr(TokenStream, "attach", attach)
    want = 6 if held_for == "all" else 400
    gw, srv, host, port = door(LaneModel(step_s=0.001), most=512)
    sock = socket.create_connection((host, port))
    try:
        post(sock, {"model": "m", "prompt": [3], "max_new": want,
                    "stream": True})
        wait_for(lambda: streams and streams[0]._q.qsize() >= 4)
        if held_for == "all":
            wait_for(lambda: streams[0].request.done)
        queued = streams[0]._q.qsize()
        gate.set()
        lines = lines_of(read_response(sock))
        assert [ln["token"] for ln in lines[:-1]] == \
            list(range(300, 300 + want))
        assert lines[-1]["done"] and lines[-1]["tokens"] == want
        c = settled(gw, done_lines=1)
        assert c["chunks_direct"] + c["chunks_by_handler"] == want
        # the end itself was in the queue: it is no token's chunk
        assert c["chunks_by_handler"] >= queued - (held_for == "all")
        assert c["opened"] == c["attached"] == c["done_lines"] == 1
        assert c["handed_back"] == c["send_failed"] == 0
        if held_for == "all":
            assert c["chunks_direct"] == 0
    finally:
        gate.set()
        sock.close()
        srv.stop(drain=True)


# -- the way out: a reader that stops reading ----------------------------------

@limited(30)
def test_a_reader_that_stops_reading_is_handed_back_and_ends_whole(
        monkeypatch):
    """Its socket fills, one send would block: that stream alone goes back
    to its handler thread, which blocks for it; the other stream's chunks
    keep coming; and once the reader reads again it gets every token."""
    real_setup = server_mod._Handler.setup

    def setup(self):                    # a send buffer the test can fill
        self.request.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        real_setup(self)

    monkeypatch.setattr(server_mod._Handler, "setup", setup)
    gw, srv, host, port = door(LaneModel(burst=4, step_s=0.001), most=4096)
    slow = socket.socket()
    slow.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2048)
    slow.connect((host, port))
    try:
        post(slow, {"model": "m", "prompt": [3], "max_new": 3000,
                    "stream": True})
        head = slow.recv(256)                   # and then it stops reading
        assert head.startswith(b"HTTP/1.1 200")
        wait_for(lambda: gw.streams.snapshot()["handed_back"] == 1, 10)
        # the other stream, start to end, while the first one is stuck
        t0 = time.monotonic()
        status, lines = stream_request(
            host, port, {"model": "m", "prompt": [4], "max_new": 400,
                         "stream": True})
        took = time.monotonic() - t0
        assert status == 200 and lines[-1]["tokens"] == 400
        assert [ln["token"] for ln in lines[:-1]] == list(range(400, 800))
        assert took < 5.0, took                 # 100 steps of a millisecond
        stuck = settled(gw, done_lines=1)
        assert stuck["opened"] == 2 and stuck["handed_back"] == 1
        lines = lines_of(read_response(slow, head))
        assert [ln["token"] for ln in lines[:-1]] == list(range(300, 3300))
        assert lines[-1]["done"] and lines[-1]["tokens"] == 3000
        c = settled(gw, done_lines=2)
        assert c["handed_back"] == 1 and c["send_failed"] == 0
        assert c["chunks_direct"] + c["chunks_by_handler"] == 3400
        assert c["chunks_by_handler"] >= 1000 and c["chunks_direct"] >= 400
        assert c["opened"] - c["done_lines"] - c["send_failed"] == 0
        # and the connection carries the next request
        post(slow, {"model": "m", "prompt": [5], "max_new": 3,
                    "stream": True})
        again = lines_of(read_response(slow))
        assert [ln.get("token") for ln in again[:-1]] == [500, 501, 502]
    finally:
        slow.close()
        srv.stop(drain=True)


# -- a send that fails ---------------------------------------------------------

@limited(15)
def test_a_client_that_disconnects_is_cancelled_and_frees_its_lane():
    model = PagedLanes(step_s=0.002)
    gw, srv, host, port = door(model, most=4096)
    sock = socket.create_connection((host, port))
    try:
        post(sock, {"model": "m", "prompt": [3], "max_new": 4000,
                    "stream": True})
        got = b""
        while got.count(b'"token"') < 5:
            got += sock.recv(4096)
        had = gw.sched.stats()["delivery"]["tokens"]
        sock.close()
        # the first send after the close may still be taken (the reset
        # comes back for it); the next one fails
        wait_for(lambda: gw.sched.stats()["finished"] == 1)
        req, = gw.sched.finished_requests()
        assert isinstance(req.error, RequestCancelled)
        # (two steps by the mechanism; the bound leaves a loaded host
        # room between the count above and the close)
        assert len(req.tokens) - had <= 50, (len(req.tokens), had)
        assert model.lanes == {} and model.pages == {}
        assert gw.sched.stats()["in_flight"] == 0
        c = settled(gw, send_failed=1)
        assert c["opened"] == 1 and c["done_lines"] == 0
        assert c["opened"] - c["done_lines"] - c["send_failed"] == 0
        # the lane serves the next client
        status, lines = stream_request(
            host, port, {"model": "m", "prompt": [4], "max_new": 3,
                         "stream": True})
        assert status == 200 and lines[-1]["tokens"] == 3
    finally:
        sock.close()
        srv.stop(drain=True)


@limited(15)
def test_a_stream_that_gets_no_token_ends_with_the_timeouts_error_line():
    model = GatedLanes(attached=set())          # admits, never emits
    gw, srv, host, port = door(model, timeout=0.2)
    sock = socket.create_connection((host, port))
    try:
        post(sock, {"model": "m", "prompt": [3], "max_new": 5,
                    "stream": True})
        lines = lines_of(read_response(sock))
        assert lines == [{"done": True, "tokens": 0, "error":
                          lines[0]["error"]}]
        assert lines[0]["error"].startswith("TimeoutError: stream: no token")
        wait_for(lambda: gw.sched.stats()["finished"] == 1)
        req, = gw.sched.finished_requests()     # cancelled, lane free
        assert isinstance(req.error, RequestCancelled)
        assert gw.sched.stats()["in_flight"] == 0
        c = settled(gw, done_lines=1)
        assert c["opened"] == 1 and c["send_failed"] == 0
        # keep-alive: the connection is whole and carries the next request
        model.attached = type("All", (), {"__contains__":
                                          lambda self, rid: True})()
        post(sock, {"model": "m", "prompt": [4], "max_new": 2,
                    "stream": True})
        lines = lines_of(read_response(sock))
        assert [ln.get("token") for ln in lines[:-1]] == [400, 401]
    finally:
        sock.close()
        srv.stop(drain=True)


# -- the bytes on the wire -----------------------------------------------------

# recorded on the parent tree (commit 9bdb2be, where the handler thread
# wrote every chunk), rid 7, LaneModel, prompt [3], max_new 5
HEAD = (b"HTTP/1.1 200 OK\r\nServer: <server>\r\nDate: <date>\r\n"
        b"Content-Type: application/jsonl\r\nTransfer-Encoding: chunked"
        b"\r\n\r\n")
TOKENS = b"".join(b'f\r\n{"token": %d}\n\r\n' % t for t in range(300, 305))
RECORDED = {
    "plain": HEAD + TOKENS + b'43\r\n{"done": true, "tokens": 5, "rid": 7, '
    b'"jid": null, "version": "1"}\n\r\n0\r\n\r\n',
    "session": HEAD + TOKENS + b'66\r\n{"done": true, "tokens": 5, "rid": 7,'
    b' "jid": null, "version": "1", "session": "s1", "resumed": false}\n\r\n'
    b"0\r\n\r\n",
    "failed": HEAD + b'48\r\n{"done": true, "tokens": 0, "error": '
    b'"ValueError: the dispatch failed"}\n\r\n0\r\n\r\n',
}


@pytest.mark.parametrize("case", sorted(RECORDED))
@limited(15)
def test_a_responses_bytes_are_the_parent_trees(monkeypatch, case):
    monkeypatch.setattr(scheduler_mod.Request, "_next_id",
                        itertools.count(7))
    model = LaneModel()
    gw, srv, host, port = door(model, lanes=2, most=16)
    if case == "failed":
        model.fail = ValueError("the dispatch failed")
    sock = socket.create_connection((host, port))
    try:
        body = {"model": "m", "prompt": [3], "max_new": 5, "stream": True}
        if case == "session":
            body["session"] = "s1"
        post(sock, body)
        raw = read_response(sock)
    finally:
        sock.close()
        srv.stop(drain=True)
    head, rest = raw.split(b"\r\n\r\n", 1)
    fields = [b"Server: <server>" if ln.startswith(b"Server: ") else
              b"Date: <date>" if ln.startswith(b"Date: ") else ln
              for ln in head.split(b"\r\n")]
    assert b"\r\n".join(fields) + b"\r\n\r\n" + rest == RECORDED[case]


# -- the trace and the counters ------------------------------------------------

def reader(name):
    path = os.path.join(ROOT, "perfbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@limited(20)
def test_first_chunk_is_emitted_once_a_request_after_its_first_token():
    """``gateway/first_chunk`` keeps its name, its ``rid`` and its place
    after ``request/token`` index 1, so the benchmark's reader of it (and
    the one of ``scheduler/deliver_out``) reads a number."""
    ring = tracer()
    ring.clear()
    gw, srv, host, port = door(LaneModel(step_s=0.001))
    t_open = time.monotonic()
    try:
        rids = []
        for k in range(4):
            status, lines = stream_request(
                host, port, {"model": "m", "prompt": [2 + k],
                             "max_new": 3 + k, "stream": True})
            assert status == 200
            rids.append(lines[-1]["rid"])
    finally:
        srv.stop(drain=True)
    t_close = time.monotonic()
    chunks = ring.events(name="gateway/first_chunk")
    assert sorted(e["args"]["rid"] for e in chunks) == sorted(rids)
    first = {e["args"]["rid"]: e["ts"]
             for e in ring.events(name="request/token")
             if e["args"]["index"] == 1}
    for e in chunks:
        assert e["ts"] >= first[e["args"]["rid"]]
    layer = {"kind": "serve", "t_open": t_open, "t_close": t_close}
    wait = reader("gateway_first_chunk_ms")(layer)
    assert isinstance(wait, float) and 0.0 <= wait < 1000.0
    lag = reader("deliver_lag_ms.serve")(layer)
    assert isinstance(lag, float) and -1000.0 < lag < 1000.0


@limited(15)
def test_streams_counts_are_in_statusz_and_count_other_answers():
    gw, srv, host, port = door(LaneModel())
    try:
        assert stream_request(host, port, {"model": "m", "prompt": [2],
                                           "max_new": 2, "stream": True}
                              )[0] == 200
        for body in ({"model": "nobody", "prompt": [2], "stream": True},
                     {"model": "m", "prompt": [], "stream": True},
                     {"model": "m", "prompt": "text"}):
            status, _ = stream_request(host, port, body)
            assert status in (400, 404)
        settled(gw, done_lines=1, non_200=3)
        conn = http.client.HTTPConnection(host, port, timeout=15)
        conn.request("GET", "/statusz")
        streams = json.loads(conn.getresponse().read())["streams"]
        conn.close()
        # (a token that beat the attach is the handler's to write)
        assert streams.pop("chunks_direct") \
            + streams.pop("chunks_by_handler") == 2
        assert streams == {"opened": 1, "attached": 1, "handed_back": 0,
                           "send_failed": 0, "done_lines": 1, "non_200": 3}
    finally:
        srv.stop(drain=True)


# -- the door ------------------------------------------------------------------

@pytest.mark.parametrize("clients", [192, 384])
@limited(40)
def test_a_storm_of_connections_is_all_accepted(clients):
    """A load generator's clients connect in one instant.  The stdlib's
    listen backlog of 5 had the kernel reset 5 to 79 of 192 such
    connections (a failed request each, before any handler ran)."""
    gw, srv, host, port = door(LaneModel(step_s=0.002), lanes=128)
    faults, go = [], threading.Event()

    def client(k):
        go.wait(10)
        try:
            status, lines = stream_request(
                host, port, {"model": "m", "prompt": [2 + k], "max_new": 8,
                             "stream": True})
            if status != 200 or lines[-1].get("tokens") != 8:
                faults.append((k, status, lines[-1:]))
        except Exception as e:
            faults.append((k, repr(e)))

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(clients)]
    try:
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
        assert not faults, (len(faults), faults[:3])
        c = settled(gw, done_lines=clients)
        assert c["opened"] == clients
        assert c["non_200"] == c["send_failed"] == 0
    finally:
        srv.stop(drain=True)
