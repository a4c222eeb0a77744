"""The load generator: HTTP streaming clients in a process of their own.

Run as ``python3 -m perfbench.loadgen <plan.json> <results.json>`` by the
serving cell.  It never imports JAX (or the program), so it neither claims
the chip nor shares the interpreter lock with the server's step loop: a
starved generator must not be read as a fast server, and how late it ran
is reported (``sent`` against ``due``).

The plan (written by the parent) holds the server's address, the absolute
``time.monotonic()`` at which traffic starts (CLOCK_MONOTONIC is one clock
for every process of the machine), when the window closes, and the
requests.  Closed loop: one thread per client, each working through its
own list.  Open loop: one dispatcher releases each request when it is due
to a pool of worker threads; a request is timed from when it was due,
whatever the wait for a free worker.

Nothing is drained: when the window closes no more is sent, and once
every request already sent has its first token (or ``grace_s`` later at
the latest; 0 for a closed loop, whose metrics end with the window) every
connection is CUT.  A request cut short is marked ``cut``, is no failure,
and the server cancels it when its next token finds no reader.
"""

from __future__ import annotations

import http.client
import json
import queue
import socket
import sys
import threading
import time
from typing import Dict, List


class _Client:
    """One keep-alive connection; one request at a time."""

    def __init__(self, addr: str, model: str, timeout: float):
        self.addr, self.model, self.timeout = addr, model, timeout
        self.conn = None
        self.lock = threading.Lock()
        self.closed = False

    def _connect(self):
        host, port = self.addr.rsplit(":", 1)
        self.conn = http.client.HTTPConnection(host, int(port),
                                               timeout=self.timeout)

    def close(self):
        with self.lock:
            self.closed = True
            sock = getattr(self.conn, "sock", None)
            if sock is not None:
                try:                    # wakes a reader blocked in recv
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

    def _finish(self, resp) -> None:
        """Read the terminating chunk, so that the connection can carry the
        next request.  The answer is complete by now: if the cut (or
        anything else) breaks this read, the connection is dropped and the
        request stands as answered."""
        try:
            resp.read()
        except (OSError, http.client.HTTPException):
            with self.lock:
                self.conn.close()
                self.conn = None

    def generate(self, req: Dict, due: float, on_first) -> Dict:
        """Send ``req`` and read its stream to the end.  ``on_first()`` is
        called once: at the first token, or at the end if none came."""
        rec = {"id": req["id"], "due": due, "sent": None, "status": None,
               "times": [], "tokens": [], "error": None, "done": None,
               "cut": False}
        body = json.dumps({"model": self.model, "prompt": req["prompt"],
                           "max_new": req["max_new"], "stream": True})
        try:
            with self.lock:
                if self.closed:
                    raise ConnectionError("generator closed")
                if self.conn is None:
                    self._connect()
            rec["sent"] = time.monotonic()
            self.conn.request("POST", "/v1/generate", body=body,
                              headers={"Content-Type": "application/json"})
            resp = self.conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = resp.read().decode(errors="replace")[:300]
            while resp.status == 200:
                line = resp.readline()
                now = time.monotonic()
                if not line:
                    rec["error"] = rec["error"] or "stream ended early"
                    break
                if not line.strip():
                    continue
                obj = json.loads(line)
                if "token" in obj:
                    if not rec["times"]:
                        on_first()
                    rec["times"].append(now)
                    rec["tokens"].append(int(obj["token"]))
                elif obj.get("done"):
                    rec["done"] = now
                    if "error" in obj:
                        rec["error"] = str(obj["error"])[:300]
                    self._finish(resp)
                    break
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
            with self.lock:
                if self.conn is not None:
                    self.conn.close()
                    self.conn = None
        if not rec["times"]:
            on_first()
        with self.lock:
            rec["cut"] = self.closed and rec["done"] is None
            if self.closed and self.conn is not None:
                self.conn.close()
                self.conn = None
        return rec


def run(plan: Dict) -> Dict:
    addr, model = plan["addr"], plan["model"]
    t0, stop_at = float(plan["t0"]), float(plan["stop_at"])
    deadline = stop_at + float(plan.get("grace_s", 0.0))
    timeout = max(5.0, deadline - time.monotonic() + 5.0)
    records: List[Dict] = []
    rec_lock = threading.Lock()
    clients: List[_Client] = []
    threads: List[threading.Thread] = []
    owed = [0]                          # sent (or due), no first token yet
    feeder = None                       # the open loop's dispatcher

    def count(by):
        with rec_lock:
            owed[0] += by

    def keep(rec):
        with rec_lock:
            records.append(rec)

    if plan["loop"] == "closed":
        per_client: Dict[int, List[Dict]] = {}
        for r in plan["requests"]:
            per_client.setdefault(int(r["client"]), []).append(r)

        def closed_client(c: _Client, todo: List[Dict]):
            for req in todo:
                now = time.monotonic()
                if now >= stop_at or c.closed:
                    return
                count(+1)
                keep(c.generate(req, now, lambda: count(-1)))

        for _cid, todo in sorted(per_client.items()):
            c = _Client(addr, model, timeout)
            clients.append(c)
            threads.append(threading.Thread(target=closed_client,
                                            args=(c, todo), daemon=True))
    else:
        todo_q: "queue.Queue" = queue.Queue()

        def worker(c: _Client):
            while True:
                item = todo_q.get()
                if item is None or c.closed:
                    return
                keep(c.generate(item, t0 + float(item["due_s"]),
                                lambda: count(-1)))

        for _ in range(int(plan["workers"])):
            c = _Client(addr, model, timeout)
            clients.append(c)
            threads.append(threading.Thread(target=worker, args=(c,),
                                            daemon=True))

        def dispatcher():
            for req in sorted(plan["requests"], key=lambda r: r["due_s"]):
                due = t0 + float(req["due_s"])
                if due >= stop_at:
                    break
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                count(+1)
                todo_q.put(req)
            for _ in clients:
                todo_q.put(None)

        feeder = threading.Thread(target=dispatcher, daemon=True)
        threads.append(feeder)

    delay = t0 - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    started = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(max(0.0, stop_at - time.monotonic()))
    # the window is closed; wait only for first tokens still owed
    if feeder is not None:
        feeder.join(timeout=max(0.0, deadline - time.monotonic()))
    while time.monotonic() < deadline and owed[0] > 0:
        time.sleep(0.01)
    cut_at = time.monotonic()
    for c in clients:
        c.close()
    for t in threads:
        t.join(timeout=max(0.0, cut_at + 10.0 - time.monotonic()))
    stuck = sum(t.is_alive() for t in threads)
    with rec_lock:
        out = list(records)
    return {"started": started, "cut_at": cut_at, "ended": time.monotonic(),
            "threads_stuck": stuck, "records": out}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print("usage: python3 -m perfbench.loadgen <plan.json> <results.json>",
              file=sys.stderr)
        return 2
    if "jax" in sys.modules:
        raise RuntimeError("the load generator must never import JAX")
    with open(argv[0], encoding="utf-8") as f:
        plan = json.load(f)
    result = run(plan)
    with open(argv[1], "w", encoding="utf-8") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
