"""Multi-process TF drop-in worker: DistributedGradientTape inside a
``tf.function`` (reference analog: the tf.function cases of
test/parallel/test_tensorflow.py — their tape allreduces are TF ops and
trace transparently; ours hosts the TCP-core grouped allreduce via
py_function at graph execution time)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402
import tensorflow as tf  # noqa: E402

import horovod_tpu.tensorflow as hvd  # noqa: E402


def rank_grads(data_rank, w):
    """The (deterministic) local gradient each rank produces, computable
    on any rank so the expected cross-rank average needs no extra comms."""
    x = np.full((4, 3), float(data_rank + 1), np.float32)
    with tf.GradientTape() as tape:
        y = tf.linalg.matmul(tf.constant(x), w)
        loss = tf.reduce_sum(y * y)
    return tape.gradient(loss, [w])[0].numpy()


def main():
    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    hvd.init()
    assert hvd.rank() == rank and hvd.size() == size

    w = tf.Variable(np.arange(6, dtype=np.float32).reshape(3, 2) / 10.0)
    unused = tf.Variable(1.0)  # tape.gradient yields None for it

    @tf.function
    def step(x):
        with tf.GradientTape() as tape:
            y = tf.linalg.matmul(x, w)
            loss = tf.reduce_sum(y * y)
        dtape = hvd.DistributedGradientTape(tape)
        return dtape.gradient(loss, [w, unused])

    x = tf.constant(np.full((4, 3), float(rank + 1), np.float32))
    gw, gu = step(x)
    assert gu is None, "None gradient must pass through the graph tape"

    expect = np.mean([rank_grads(r, w) for r in range(size)], axis=0)
    np.testing.assert_allclose(gw.numpy(), expect, rtol=1e-5)

    # eager path stays equivalent to the traced path
    with tf.GradientTape() as tape:
        y = tf.linalg.matmul(x, w)
        loss = tf.reduce_sum(y * y)
    eg = hvd.DistributedGradientTape(tape).gradient(loss, [w])[0]
    np.testing.assert_allclose(eg.numpy(), expect, rtol=1e-5)

    # sparse embedding grads (IndexedSlices) stay sparse inside the
    # tf.function: every rank's (indices, values) allgather and the
    # values average, so densifying reproduces the cross-rank mean
    emb = tf.Variable(np.zeros((5, 2), np.float32))

    @tf.function
    def emb_step(ids):
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(tf.nn.embedding_lookup(emb, ids))
        return hvd.DistributedGradientTape(tape).gradient(loss, [emb])[0]

    g = emb_step(tf.constant([rank, rank]))  # rank r touches row r twice
    assert isinstance(g, tf.IndexedSlices), type(g)
    assert int(tf.shape(g.indices)[0]) == 2 * size  # gathered, not densified
    exp = np.zeros((5, 2), np.float32)
    for r in range(size):
        exp[r] += 2.0
    exp /= size
    np.testing.assert_allclose(
        np.asarray(tf.convert_to_tensor(g)), exp, rtol=1e-6)

    # two tapes over the SAME variables in one traced step (WGAN-GP
    # style): identical gradient structure, so only the trace-time
    # graph-unique name suffix keeps their allreduces apart
    def local_pair(data_rank):
        x_r = tf.constant(np.full((4, 3), float(data_rank + 1), np.float32))
        with tf.GradientTape() as t1:
            l1 = tf.reduce_sum(tf.linalg.matmul(x_r, w))
        with tf.GradientTape() as t2:
            l2 = tf.reduce_sum(tf.linalg.matmul(x_r, w) ** 2)
        return (t1.gradient(l1, [w])[0].numpy(),
                t2.gradient(l2, [w])[0].numpy())

    @tf.function
    def double_step(xx):
        with tf.GradientTape() as t1:
            l1 = tf.reduce_sum(tf.linalg.matmul(xx, w))
        with tf.GradientTape() as t2:
            l2 = tf.reduce_sum(tf.linalg.matmul(xx, w) ** 2)
        # distinct name_scopes: the uniquifier must keep the scope path
        # ('gen/tfgrad' vs 'disc/tfgrad'), not just the leaf name
        with tf.name_scope("gen"):
            g1 = hvd.DistributedGradientTape(t1).gradient(l1, [w])[0]
        with tf.name_scope("disc"):
            g2 = hvd.DistributedGradientTape(t2).gradient(l2, [w])[0]
        return g1, g2

    g1, g2 = double_step(x)
    pairs = [local_pair(r) for r in range(size)]
    np.testing.assert_allclose(
        g1.numpy(), np.mean([p[0] for p in pairs], axis=0), rtol=1e-5)
    np.testing.assert_allclose(
        g2.numpy(), np.mean([p[1] for p in pairs], axis=0), rtol=1e-5)

    # a lone Variable source keeps its structure at size > 1 too
    with tf.GradientTape() as tape:
        y = tf.linalg.matmul(x, w)
        loss = tf.reduce_sum(y * y)
    sg = hvd.DistributedGradientTape(tape).gradient(loss, w)
    assert not isinstance(sg, (list, tuple))
    np.testing.assert_allclose(sg.numpy(), expect, rtol=1e-5)

    # fp16 compression through the traced optimizer path: compressed
    # wire dtype, original dtype after decompress, ranks agree
    wc = tf.Variable(np.ones((3,), np.float32))
    copt = hvd.DistributedOptimizer(
        tf.keras.optimizers.SGD(1.0), compression=hvd.Compression.fp16)

    @tf.function
    def cstep(g):
        copt.apply_gradients([(g, wc)])

    cstep(tf.constant(np.full(3, float(rank + 1), np.float32)))
    np.testing.assert_allclose(wc.numpy(),
                               1.0 - (sum(range(size)) + size) / size,
                               rtol=1e-3)

    # keras model.fit at size 2: the wrapped optimizer's graph-mode sync
    # (keras compiles train_step into a tf.function) plus the broadcast
    # callback must leave every rank with IDENTICAL weights
    import horovod_tpu.keras as khvd
    tf.random.set_seed(rank)  # deliberately different init per rank
    model = tf.keras.Sequential([tf.keras.layers.Dense(1, input_shape=(4,))])
    model.compile(
        optimizer=khvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.05)),
        loss="mse")
    rng = np.random.RandomState(0)
    fx = rng.randn(32, 4).astype(np.float32)
    fy = (fx @ np.asarray([[1.0], [2.0], [3.0], [4.0]], np.float32))
    mine = slice(rank * 16, (rank + 1) * 16)
    model.fit(fx[mine], fy[mine], epochs=2, batch_size=8, verbose=0,
              callbacks=[khvd.callbacks.BroadcastGlobalVariablesCallback(0)])
    final = np.concatenate([w.reshape(-1) for w in model.get_weights()])
    gathered = hvd.allgather(tf.constant(final[None, :]))
    np.testing.assert_allclose(np.asarray(gathered)[0],
                               np.asarray(gathered)[1], rtol=1e-6)

    hvd.shutdown()
    print("tf_worker ok")


if __name__ == "__main__":
    main()
