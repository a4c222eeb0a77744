"""Large-vocab sparse-vs-dense embedding benchmark (r4 VERDICT #4 — the
reference built SparseRowMatrix/SparseParameterDistribution because dense
updates at CTR vocab sizes were unaffordable; this measures whether
``embedding(is_sparse=True)`` actually wins on TPU, where the dense
scatter-add is MXU/HBM-native).

Model: embedding [V, D] over a batch of id sequences -> sequence_pool(sum)
-> fc -> softmax-xent, adam.  Per step the batch touches at most
batch*seq_len distinct rows, so the dense path moves the FULL [V, D] table
(grad buffer + two adam moments + param) while the sparse path moves only
the touched rows' values and (lazily) their moments.

Run: PYTHONPATH=/root/repo:$PYTHONPATH \
         python tools/sparse_bench.py --vocab 1500000
"""

import argparse
import time

import numpy as np


def bench(vocab, dim, batch, seq, steps, is_sparse, optimizer):
    import jax

    from paddle_tpu import fluid
    from paddle_tpu.fluid import make_seq

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        words = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                  lod_level=1)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(input=words, size=[vocab, dim],
                                     is_sparse=is_sparse)
        pooled = fluid.layers.sequence_pool(input=emb, pool_type="sum")
        pred = fluid.layers.fc(input=pooled, size=2, act="softmax")
        cost = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        opt = (fluid.optimizer.Adam(learning_rate=1e-3) if
               optimizer == "adam" else
               fluid.optimizer.SGD(learning_rate=0.1))
        opt.minimize(cost)

    rng = np.random.RandomState(0)
    seqs = [rng.randint(0, vocab, (seq, 1)) for _ in range(batch)]
    feed = {"words": make_seq(seqs, dtype=np.int32),
            "label": rng.randint(0, 2, (batch, 1)).astype(np.int64)}
    exe = fluid.Executor(fluid.TPUPlace(0))
    with fluid.scope_guard(scope):
        exe.run(startup)
        ca = exe.cost_analysis(main, feed=feed, fetch_list=[cost])
        for _ in range(3):
            out = exe.run(main, feed=feed, fetch_list=[cost],
                          return_numpy=False)[0]
        float(np.asarray(out))
        t0 = time.time()
        for _ in range(steps):
            out = exe.run(main, feed=feed, fetch_list=[cost],
                          return_numpy=False)[0]
        float(np.asarray(out))          # D2H sync
        dt = (time.time() - t0) / steps
    return dt, ca.get("bytes accessed", 0.0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=1500000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--opt", default="adam")
    args = ap.parse_args()
    import jax

    print(f"device={jax.devices()[0].device_kind} vocab={args.vocab} "
          f"dim={args.dim} batch={args.batch} seq={args.seq} opt={args.opt}")
    for sparse in (False, True):
        dt, nbytes = bench(args.vocab, args.dim, args.batch, args.seq,
                           args.steps, sparse, args.opt)
        print(f"is_sparse={sparse!s:5}  {dt*1e3:9.2f} ms/step  "
              f"cost-analysis bytes {nbytes/1e9:7.2f} GB")


if __name__ == "__main__":
    main()
