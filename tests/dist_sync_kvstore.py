"""Worker script for the dist_sync kvstore invariant test.

Reference counterpart: ``tests/nightly/dist_sync_kvstore.py:28-80`` — every
worker pushes rank-dependent values and asserts the EXACT aggregate on all
workers, covering dense keys, a big range-sharded key, and row_sparse.

Run via the local launcher (the pytest wrapper in test_dist_kvstore.py
does this automatically):

    python tools/launch.py -n 4 -s 2 python tests/dist_sync_kvstore.py
"""
import os

# every launcher role imports jax; the PS fleet is a CPU-only tool
# (README "CPU-only tools"), so pin before the first backend touch
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import mxnet_tpu as mx  # noqa: E402

RATE = 2.0
ITERS = 3
# 'big' exceeds MXNET_KVSTORE_BIGARRAY_BOUND (set low by the test harness)
# so it range-shards across every server
SHAPES = {"3": (4, 4), "99": (50, 50), "big": (100, 60)}


def test_dense(kv, nworkers, rank):
    for k, s in SHAPES.items():
        kv.init(k, mx.nd.ones(s))
    tri = nworkers * (nworkers + 1) // 2
    for it in range(ITERS):
        for k, s in SHAPES.items():
            kv.push(k, mx.nd.ones(s) * (rank + 1))
            out = mx.nd.zeros(s)
            kv.pull(k, out=out)
            want = 1.0 - RATE * (it + 1) * tri
            got = out.asnumpy()
            assert np.all(got == want), \
                "dense key %s iter %d: got %r want %r" % (k, it, got.flat[0], want)


def test_row_sparse(kv, nworkers, rank, key="rsp", shape=None):
    shape = shape or (4 * nworkers + 4, 8)
    kv.init(key, mx.nd.zeros(shape))
    # every worker touches shared row 0 plus its own row (rank+1)
    rows = np.array([0, rank + 1], np.int64)
    dense = np.zeros(shape, np.float32)
    dense[rows] = rank + 1
    grad = mx.nd.sparse.row_sparse_array(
        (dense[rows], rows), shape=shape)
    kv.push("rsp", grad)

    all_rows = mx.nd.array(np.arange(shape[0]), dtype="int64")
    out = mx.nd.zeros(shape)
    kv.row_sparse_pull("rsp", out=out, row_ids=all_rows)
    got = out.asnumpy()

    want = np.zeros(shape, np.float32)
    tri = nworkers * (nworkers + 1) // 2
    want[0] = -RATE * tri
    for r in range(nworkers):
        want[r + 1] += -RATE * (r + 1)
    assert np.all(got == want), \
        "row_sparse: got rows %r want %r" % (got[:nworkers + 2, 0],
                                             want[:nworkers + 2, 0])


def test_bucketed_push_pull_all(kv, nworkers, rank):
    """Bucketed gradient all-reduce (kvstore.push_pull_all): every worker
    contributes rank-dependent grads for several keys; the flat-bucket
    transport round must return the exact global sum for each key."""
    shapes = [(5, 3), (7,), (2, 2, 2), (11,)]
    keys = ["pb%d" % i for i in range(len(shapes))]
    for k, s in zip(keys, shapes):
        kv.init(k, mx.nd.zeros(s))
    tri = nworkers * (nworkers + 1) // 2
    for it in range(2):
        vals = [mx.nd.ones(s) * (rank + 1 + it) for s in shapes]
        outs = kv.push_pull_all(keys, vals)
        want = tri + nworkers * it
        for k, o in zip(keys, outs):
            got = o.asnumpy()
            assert np.all(got == want), \
                "bucketed key %s iter %d: got %r want %r" \
                % (k, it, got.flat[0], want)
    kv.barrier()


def main():
    kv = mx.kv.create("dist_sync")
    nworkers, rank = kv.num_workers, kv.rank
    test_bucketed_push_pull_all(kv, nworkers, rank)
    kv.set_optimizer(mx.optimizer.create("test", rescale_grad=RATE))
    test_dense(kv, nworkers, rank)
    test_row_sparse(kv, nworkers, rank)
    kv.barrier()
    # liveness surface: everyone is still here (ref kvstore.h:328)
    assert kv.get_num_dead_node() == 0
    print("worker %d/%d: dist_sync invariants OK" % (rank, nworkers))


if __name__ == "__main__":
    main()
