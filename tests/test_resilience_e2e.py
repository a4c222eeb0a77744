"""Multi-process fault-tolerance scenarios (marked slow; tier-1 runs the
fast deterministic halves in test_resilience.py).

The flagship test is the chaos end-to-end: a seeded FaultInjector
SIGKILLs the worker mid-epoch (kill-after-N-leases) while the test
restarts the master out from under it; the supervised launcher respawns
the worker, ResilientTrainer resumes from the newest valid checkpoint,
the recovered master re-dispatches the expired leases, and the job
finishes with every chunk processed and zero lost tasks — the
reference's whole fault-tolerance story (go/master/service.go +
go/pserver/service.go) in one deterministic scenario.
"""

import os
import sys
import textwrap
import threading
import time

import pytest

from paddle_tpu.launch import launch
from paddle_tpu.parallel import MasterServer, TaskQueue
from paddle_tpu.resilience import FaultInjector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def _clean_env(extra=None):
    """CPU-only env for spawned workers (same hygiene as
    test_distributed_multiproc._run: CPU platform, repo on path)."""
    env = {"JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                            "")}
    env.update(extra or {})
    return env


# -- elastic launcher --------------------------------------------------------

CRASHY = """
    import os, sys
    marker_dir = sys.argv[1]
    n = len(os.listdir(marker_dir))
    open(os.path.join(marker_dir, f"inc-{n}"), "w").close()
    if n < 2:
        os._exit(7)          # die hard on the first two incarnations
    sys.exit(0)
"""


def test_elastic_launcher_restarts_dead_rank_until_success(tmp_path):
    """--max-restarts: a rank dying non-zero is respawned (same rank,
    same env) until it succeeds or the budget runs out."""
    script = str(tmp_path / "crashy.py")
    open(script, "w").write(textwrap.dedent(CRASHY))
    mdir = str(tmp_path / "marks")
    os.makedirs(mdir)
    rc = launch(1, [script, mdir], env_extra=_clean_env(),
                max_restarts=3, kill_grace=2.0)
    assert rc == 0
    assert len(os.listdir(mdir)) == 3            # 1 first run + 2 restarts


def test_elastic_launcher_budget_exhaustion_fails_fast(tmp_path):
    script = str(tmp_path / "crashy.py")
    open(script, "w").write(textwrap.dedent(CRASHY))
    mdir = str(tmp_path / "marks")
    os.makedirs(mdir)
    rc = launch(1, [script, mdir], env_extra=_clean_env(),
                max_restarts=1, kill_grace=2.0)
    assert rc == 7                               # second crash is fatal
    assert len(os.listdir(mdir)) == 2


def test_launcher_writes_per_rank_logs_across_restarts(tmp_path):
    script = str(tmp_path / "talky.py")
    open(script, "w").write(textwrap.dedent("""
        import os, sys
        mark = sys.argv[1]
        first = not os.path.exists(mark)
        open(mark, "a").close()
        print("hello from incarnation", flush=True)
        sys.exit(1 if first else 0)
    """))
    logdir = str(tmp_path / "logs")
    rc = launch(1, [script, str(tmp_path / "mark")],
                env_extra=_clean_env(), max_restarts=2, kill_grace=2.0,
                log_dir=logdir)
    assert rc == 0
    log = open(os.path.join(logdir, "rank-0.log")).read()
    # both incarnations appended to the same rank log
    assert log.count("hello from incarnation") == 2


# -- the chaos end-to-end ----------------------------------------------------

E2E_WORKER = """
    import os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    addr, ckpt_dir, losses_path = sys.argv[1:4]

    from paddle_tpu import fluid
    from paddle_tpu.parallel import MasterClient
    from paddle_tpu.resilience import ResilientTrainer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [4], "float32")
        y = fluid.layers.data("y", [1], "float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())

    W = np.array([1.0, -2.0, 0.5, 3.0], np.float32)

    def read_chunk(seed):
        r = np.random.RandomState(seed)
        out = []
        for _ in range(4):                  # 4 record-batches per chunk
            xs = r.randn(8, 4).astype(np.float32)
            out.append((xs, xs @ W[:, None]))
        return out

    client = MasterClient(addr, worker=f"pid-{os.getpid()}")
    trainer = ResilientTrainer(ckpt_dir, client, read_chunk,
                               program=main, scope=scope,
                               save_interval_steps=1, poll_interval=0.05)

    def train_step(rec, step):
        xs = np.asarray(rec[0], np.float32)
        ys = np.asarray(rec[1], np.float32)
        l, = exe.run(main, feed={"x": xs, "y": ys}, fetch_list=[loss])
        with open(losses_path, "a") as f:
            f.write(f"{step} {float(np.asarray(l))}\\n")

    fresh = []
    with fluid.scope_guard(scope):
        final = trainer.run(train_step,
                            init_fn=lambda: (fresh.append(1),
                                             exe.run(startup)))
    if not fresh:
        print("RESUMED-FROM-CHECKPOINT", flush=True)
    print("WORKER-DONE step", final, flush=True)
"""

N_CHUNKS = 8


def test_chaos_end_to_end_worker_kills_and_master_restart(tmp_path):
    """Acceptance scenario: seeded chaos SIGKILLs the worker upon its
    3rd lease of every incarnation, the test restarts the master
    mid-epoch (recovering from its auto-snapshot), the supervised
    launcher respawns the worker, and the job still completes: all 8
    chunks done, 0 lost, loss decreased, ResilientTrainer resumed from a
    checkpoint, and every journaled injection decision replays exactly
    from the seed."""
    script = str(tmp_path / "worker.py")
    open(script, "w").write(textwrap.dedent(E2E_WORKER))
    snap = str(tmp_path / "master.snap")
    ckpt = str(tmp_path / "ckpt")
    losses_path = str(tmp_path / "losses.txt")
    journal = str(tmp_path / "chaos.journal")
    logdir = str(tmp_path / "logs")
    seed = 7

    queue = TaskQueue(timeout_secs=1.0, failure_max=10)
    queue.set_dataset(list(range(N_CHUNKS)))
    server = MasterServer(queue, snapshot_path=snap, snapshot_every=1)
    addr = server.start()
    host, port = addr.split(":")

    env = _clean_env({
        "PADDLE_TPU_CHAOS": "master.http=0.05",
        "PADDLE_TPU_CHAOS_SEED": str(seed),
        "PADDLE_TPU_CHAOS_KILL_AFTER": "3",
        "PADDLE_TPU_CHAOS_LOG": journal,
    })
    rc_box = {}

    def run_job():
        rc_box["rc"] = launch(
            1, [script, addr, ckpt, losses_path], env_extra=env,
            max_restarts=12, kill_grace=5.0, log_dir=logdir)

    th = threading.Thread(target=run_job)
    th.start()

    # let the first incarnation make progress, then crash the master
    deadline = time.monotonic() + 180
    while (time.monotonic() < deadline
           and server.queue.counts()["done"] < 2):
        time.sleep(0.1)
    assert server.queue.counts()["done"] >= 2, "worker never progressed"
    server.stop()                                # snapshot + gone
    time.sleep(0.5)                              # worker retries meanwhile
    server2 = MasterServer(None, host=host, port=int(port),
                           snapshot_path=snap)
    server2.start()

    th.join(timeout=420)
    assert not th.is_alive(), "supervised job did not finish"
    try:
        assert rc_box["rc"] == 0

        # 0 lost tasks: every chunk processed, none discarded or leased
        counts = server2.queue.counts()
        assert counts["done"] == N_CHUNKS, counts
        assert counts["failed"] == 0 and counts["pending"] == 0, counts
        assert server2.queue.all_done()

        # worker actually died and was respawned by the supervisor, and
        # at least one incarnation resumed from a checkpoint
        log = open(os.path.join(logdir, "rank-0.log")).read()
        assert "RESUMED-FROM-CHECKPOINT" in log
        assert log.count("WORKER-DONE") == 1     # exactly one clean exit
        kills = [ln for ln in open(journal) if ln.startswith("# kill-self")]
        assert kills, "chaos never killed the worker"

        # training made progress across all the carnage
        losses = [float(ln.split()[1]) for ln in open(losses_path)]
        assert len(losses) >= N_CHUNKS * 4       # every record trained on
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

        # determinism: every journaled draw replays exactly from the
        # seed, and repeated (point, index) pairs — the same draw made
        # by different incarnations — agree bit-for-bit, which is what
        # "same seed, same injection schedule on re-run" means
        draws = {}
        n_lines = 0
        for ln in open(journal):
            if ln.startswith("#") or not ln.strip():
                continue
            point, index, value, fired = ln.split()
            n_lines += 1
            want = FaultInjector.decision(seed, point, int(index))
            assert abs(float(value) - want) < 1e-9
            prev = draws.setdefault((point, int(index)), (value, fired))
            assert prev == (value, fired)
        assert n_lines > 0 and len(draws) < n_lines, \
            "expected repeated draws across worker incarnations"
    finally:
        server2.stop()
