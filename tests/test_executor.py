"""Executor + training-step tests (reference pattern: tests/test_ops.py dual
executors + examples/runner/parallel/validate_results.py single-vs-parallel
numerical parity)."""
import numpy as np
import pytest

import hetu_tpu as ht


def _mlp_graph(seed=0):
    rng = np.random.RandomState(seed)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y_")
    w1 = ht.Variable("w1", value=rng.randn(8, 16).astype(np.float32) * 0.1)
    b1 = ht.Variable("b1", value=np.zeros(16, np.float32))
    w2 = ht.Variable("w2", value=rng.randn(16, 4).astype(np.float32) * 0.1)
    h = ht.relu_op(ht.linear_op(x, w1, b1))
    logits = ht.matmul_op(h, w2)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y_), [0])
    return x, y_, loss, logits, [w1, b1, w2]


def _data(seed=1, n=32):
    rng = np.random.RandomState(seed)
    xv = rng.randn(n, 8).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, n)]
    return xv, yv


def test_sgd_training_decreases_loss():
    x, y_, loss, logits, _ = _mlp_graph()
    opt = ht.optim.SGDOptimizer(learning_rate=0.5)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]})
    xv, yv = _data()
    losses = [float(ex.run("train", feed_dict={x: xv, y_: yv})[0].asnumpy())
              for _ in range(30)]
    assert losses[-1] < losses[0] * 0.7, losses


def test_sgd_matches_numpy():
    """One SGD step == manual numpy gradient step for a linear regression."""
    xv = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    yv = np.array([[1.0], [0.0]], np.float32)
    w0 = np.array([[0.5], [-0.5]], np.float32)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y")
    w = ht.Variable("w", value=w0.copy())
    pred = ht.matmul_op(x, w)
    diff = pred - y_
    loss = ht.reduce_mean_op(diff * diff, [0, 1])
    train_op = ht.optim.SGDOptimizer(learning_rate=0.1).minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]})
    ex.run("train", feed_dict={x: xv, y_: yv})
    # manual: dL/dw = 2/N * x^T (xw - y)
    grad = 2.0 / 2 * xv.T @ (xv @ w0 - yv)
    np.testing.assert_allclose(np.asarray(ex.var_values[w]), w0 - 0.1 * grad,
                               rtol=1e-5, atol=1e-7)


def test_gradients_fetch():
    x, y_, loss, logits, (w1, b1, w2) = _mlp_graph()
    gw1, gw2 = ht.gradients(loss, [w1, w2])
    ex = ht.Executor([loss, gw1, gw2])
    xv, yv = _data()
    lv, g1, g2 = ex.run(feed_dict={x: xv, y_: yv},
                        convert_to_numpy_ret_vals=True)
    assert g1.shape == (8, 16) and g2.shape == (16, 4)
    assert np.abs(g2).sum() > 0


def _run_optimizer(opt, steps=3):
    xv, yv = _data(3)
    x, y_, loss, logits, params = _mlp_graph(2)
    train_op = opt.minimize(loss)
    ex = ht.Executor({"train": [loss, train_op]})
    for _ in range(steps):
        out = ex.run("train", feed_dict={x: xv, y_: yv})
    return float(out[0].asnumpy())


def test_all_optimizers_step():
    for opt in [ht.optim.SGDOptimizer(0.1),
                ht.optim.MomentumOptimizer(0.1, momentum=0.9),
                ht.optim.MomentumOptimizer(0.1, momentum=0.9, nesterov=True),
                ht.optim.AdaGradOptimizer(0.1, initial_accumulator_value=0.1),
                ht.optim.AdamOptimizer(0.01),
                ht.optim.AdamWOptimizer(0.01, weight_decay=0.01),
                ht.optim.LambOptimizer(0.01, weight_decay=0.01)]:
        final = _run_optimizer(opt)
        assert np.isfinite(final)


def test_adam_matches_numpy():
    w0 = np.array([[1.0, 2.0]], np.float32)
    x = ht.placeholder_op("x")
    w = ht.Variable("w", value=w0.copy())
    loss = ht.reduce_mean_op(ht.mul_op(w, x), [0, 1])  # dL/dw = x/2
    opt = ht.optim.AdamOptimizer(learning_rate=0.1, beta1=0.9, beta2=0.999,
                                 epsilon=1e-7)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]})
    xv = np.array([[2.0, 4.0]], np.float32)
    ex.run("train", feed_dict={x: xv})
    g = xv / 2
    m = 0.1 * g
    v = 0.001 * g * g
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.999)
    ref = w0 - 0.1 * mhat / (np.sqrt(vhat) + 1e-7)
    np.testing.assert_allclose(np.asarray(ex.var_values[w]), ref, rtol=1e-5)


def test_batchnorm_updates_running_stats():
    rng = np.random.RandomState(0)
    xv = rng.randn(16, 3, 4, 4).astype(np.float32) * 2 + 1
    x = ht.placeholder_op("x")
    scale = ht.init.ones((3,), name="scale")
    bias = ht.init.zeros((3,), name="bias")
    bn = ht.batch_normalization_op(x, scale, bias, momentum=0.5)
    loss = ht.reduce_mean_op(bn, [0, 1, 2, 3])
    train_op = ht.optim.SGDOptimizer(0.01).minimize(loss)
    ex = ht.Executor({"train": [loss, train_op], "eval": [bn]})
    ex.run("train", feed_dict={x: xv})
    rm = np.asarray(ex.var_values[bn.running_mean])
    batch_mean = xv.mean((0, 2, 3))
    np.testing.assert_allclose(rm, 0.5 * batch_mean, rtol=1e-4)
    # eval path uses running stats (not batch stats)
    out = ex.run("eval", feed_dict={x: xv}, convert_to_numpy_ret_vals=True)[0]
    assert np.isfinite(out).all()


def test_dropout_train_vs_eval():
    xv = np.ones((64, 64), np.float32)
    x = ht.placeholder_op("x")
    d = ht.dropout_op(x, 0.5)
    s = ht.reduce_mean_op(d, [0, 1])
    w = ht.Variable("w", value=np.ones((1,), np.float32))
    loss = s * ht.reduce_mean_op(w, [0])
    ex = ht.Executor({"train": [loss, ht.optim.SGDOptimizer(0.0).minimize(loss)],
                      "eval": [d]}, seed=7)
    lv = float(ex.run("train", feed_dict={x: xv})[0].asnumpy())
    assert 0.8 < lv < 1.2 and lv != 1.0  # masked+rescaled mean ≈ 1
    ev = ex.run("eval", feed_dict={x: xv}, convert_to_numpy_ret_vals=True)[0]
    np.testing.assert_allclose(ev, xv)  # identity at inference


def test_save_load_roundtrip(tmp_path):
    x, y_, loss, logits, params = _mlp_graph()
    opt = ht.optim.AdamOptimizer(0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]})
    xv, yv = _data()
    for _ in range(3):
        ex.run("train", feed_dict={x: xv, y_: yv})
    ckpt = str(tmp_path / "ck.bin")
    ex.save(ckpt)
    w_after = {n.name: np.asarray(v) for n, v in ex.var_values.items()}
    for _ in range(2):
        ex.run("train", feed_dict={x: xv, y_: yv})
    ex.load(ckpt)
    for n, v in ex.var_values.items():
        np.testing.assert_allclose(np.asarray(v), w_after[n.name], rtol=1e-6)
    assert ex.step_counter == 3


def test_lr_scheduler_effective():
    sched = ht.optim.StepScheduler(1.0, step_size=2, gamma=0.1)
    assert sched.get(0) == 1.0 and np.isclose(sched.get(2), 0.1) \
        and np.isclose(sched.get(4), 0.01)
    ms = ht.optim.MultiStepScheduler(1.0, [2, 4], 0.5)
    assert ms.get(1) == 1.0 and np.isclose(ms.get(3), 0.5) and np.isclose(ms.get(5), 0.25)
    ex = ht.optim.ExponentialScheduler(1.0, 0.9)
    np.testing.assert_allclose(ex.get(3), 0.9 ** 3)
    pl = ht.optim.ReduceOnPlateauScheduler(1.0, patience=1, factor=0.1)
    for m in [1.0, 1.0, 1.0, 1.0]:
        pl.step(m)
    assert pl.get(0) < 1.0


def test_dataloader_and_batch_num():
    xv, yv = _data(5, 40)
    x = ht.dataloader_op([ht.Dataloader(xv, 8, "train")])
    y_ = ht.dataloader_op([ht.Dataloader(yv, 8, "train")])
    w = ht.Variable("w", value=np.zeros((8, 4), np.float32))
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(ht.matmul_op(x, w), y_),
                             [0])
    ex = ht.Executor({"train": [loss, ht.optim.SGDOptimizer(0.1).minimize(loss)]})
    assert ex.get_batch_num("train") == 5
    for _ in range(5):
        out = ex.run("train")
    assert np.isfinite(float(out[0].asnumpy()))


def test_imagenet_folder_loader(tmp_path):
    """ImageNet-layout loader: real folder decode + synthetic fallback
    (reference data.py ImageNet path)."""
    from PIL import Image
    from hetu_tpu.data import ImageNetFolder
    root = tmp_path / "train"
    rng = np.random.RandomState(0)
    for cname in ("class_a", "class_b"):
        d = root / cname
        d.mkdir(parents=True)
        for i in range(4):
            arr = (rng.rand(40, 52, 3) * 255).astype(np.uint8)
            Image.fromarray(arr).save(d / f"im{i}.jpeg")
    ds = ImageNetFolder(str(root), image_size=32, batch_size=4, seed=1)
    assert ds.num_classes == 2 and len(ds) == 2
    batches = list(ds)
    assert len(batches) == 2
    x, y = batches[0]
    assert x.shape == (4, 3, 32, 32) and x.dtype == np.float32
    assert y.shape == (4,) and set(y) <= {0, 1}
    # normalized: roughly centered
    assert abs(float(x.mean())) < 3.0

    # synthetic fallback when the directory is absent
    ds2 = ImageNetFolder(str(tmp_path / "missing"), image_size=16,
                         batch_size=2, synthetic_batches=3, num_classes=5)
    bs = list(ds2)
    assert len(bs) == 3 and bs[0][0].shape == (2, 3, 16, 16)


def test_streamed_checkpoint_full_resume(tmp_path):
    """Train 3 steps -> save -> fresh executor -> load -> step 4 is
    BITWISE identical to the uninterrupted run (params + optimizer state +
    PS table + step counter all round-trip), on the dp8 mesh."""
    from hetu_tpu.ps import EmbeddingStore

    rng = np.random.RandomState(0)
    vocab, dim, batch = 32, 8, 16
    table0 = rng.randn(vocab, dim).astype(np.float32) * 0.1
    ids_v = rng.randint(0, vocab, batch)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, batch)]
    w0 = rng.randn(dim, 4).astype(np.float32) * 0.3

    def build(store, table):
        ids = ht.placeholder_op("ids")
        y_ = ht.placeholder_op("y")
        h = ht.ps_embedding_lookup_op((store, table), ids, width=dim)
        w = ht.Variable("w", value=w0.copy(), trainable=True)
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(h, w), y_), [0])
        opt = ht.optim.AdamOptimizer(0.01)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=5,
                         dist_strategy=ht.dist.DataParallel())
        return ex, ids, y_, w

    def steps(ex, ids, y_, n):
        return [float(ex.run("train", feed_dict={ids: ids_v, y_: yv}
                             )[0].asnumpy()) for _ in range(n)]

    # uninterrupted 4-step run
    st_a = EmbeddingStore()
    t_a = st_a.init_table(vocab, dim, opt="adam", lr=0.05, seed=0)
    st_a.set_data(t_a, table0.copy())
    ex_a, ids_a, y_a, w_a = build(st_a, t_a)
    losses_a = steps(ex_a, ids_a, y_a, 4)

    # interrupted: 3 steps, checkpoint, resume in a FRESH executor+store
    st_b = EmbeddingStore()
    t_b = st_b.init_table(vocab, dim, opt="adam", lr=0.05, seed=0)
    st_b.set_data(t_b, table0.copy())
    ex_b, ids_b, y_b, w_b = build(st_b, t_b)
    steps(ex_b, ids_b, y_b, 3)
    ckpt = str(tmp_path / "ckpt")
    ex_b.save(ckpt)

    st_c = EmbeddingStore()
    t_c = st_c.init_table(vocab, dim, opt="adam", lr=0.05, seed=99)  # junk init
    ex_c, ids_c, y_c, w_c = build(st_c, t_c)
    ex_c.load(ckpt)
    assert ex_c.step_counter == 3
    np.testing.assert_array_equal(st_c.get_data(t_c), st_b.get_data(t_b))
    loss4 = steps(ex_c, ids_c, y_c, 1)[0]
    assert loss4 == losses_a[3], (loss4, losses_a[3])
    np.testing.assert_array_equal(np.asarray(ex_c.var_values[w_c]),
                                  np.asarray(ex_a.var_values[w_a]))


def test_checkpoint_resumes_dataloader_position(tmp_path):
    """Exact resume with dataloader-fed inputs: the restored run continues
    at the NEXT batch (incl. shuffle order mid-epoch and outstanding
    prefetch/peek), matching an uninterrupted run bitwise."""
    from hetu_tpu.data.dataloader import Dataloader, DataloaderOp
    from hetu_tpu.ps import EmbeddingStore

    rng = np.random.RandomState(0)
    vocab, dim, batch, steps_total = 24, 4, 6, 7
    ids_stream = rng.randint(0, vocab, (40 * batch,)).astype(np.int64)
    table0 = rng.randn(vocab, dim).astype(np.float32) * 0.1
    yv = np.eye(2, dtype=np.float32)[rng.randint(0, 2, batch)]

    def build():
        st = EmbeddingStore()
        t = st.init_table(vocab, dim, opt="adam", lr=0.05, seed=0)
        st.set_data(t, table0.copy())
        dl = DataloaderOp([Dataloader(ids_stream, batch, "train",
                                      shuffle=True, seed=3)], name="ids")
        y_ = ht.placeholder_op("y")
        h = ht.ps_embedding_lookup_op((st, t), dl, width=dim)
        w = ht.Variable("w", value=np.full((dim, 2), 0.3, np.float32),
                        trainable=True)
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
            ht.matmul_op(h, w), y_), [0])
        ex = ht.Executor(
            {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
            seed=1)
        return ex, y_, st, t

    def run(ex, y_, n):
        return [float(ex.run("train", feed_dict={y_: yv})[0].asnumpy())
                for _ in range(n)]

    ex_a, y_a, st_a, t_a = build()
    losses_a = run(ex_a, y_a, steps_total)

    ex_b, y_b, st_b, t_b = build()
    run(ex_b, y_b, 4)
    ckpt = str(tmp_path / "dl_ckpt")
    ex_b.save(ckpt)

    ex_c, y_c, st_c, t_c = build()
    ex_c.load(ckpt)
    losses_c = run(ex_c, y_c, steps_total - 4)
    np.testing.assert_array_equal(losses_a[4:], losses_c)
    np.testing.assert_array_equal(st_c.get_data(t_c), st_a.get_data(t_a))


def test_remat_training_parity():
    """Executor(remat=True) recomputes activations in the backward pass;
    the training trajectory must be identical to the non-remat run."""
    def run(remat):
        x, y_, loss, logits, params = _mlp_graph()
        opt = ht.optim.AdamOptimizer(0.01)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                         remat=remat)
        xv, yv = _data()
        return [float(ex.run("train", feed_dict={x: xv, y_: yv})[0].asnumpy())
                for _ in range(4)]

    np.testing.assert_allclose(run(False), run(True), rtol=1e-6)


@pytest.mark.slow     # 12s at HEAD (ISSUE 12 tier-1 budget);
# bf16 training stays via the test_bf16_parity sweep
def test_mixed_precision_bf16_trains_with_f32_masters():
    """The flagship's compute_dtype path (the bert cell on TPU): bf16
    inside the step, fp32 master weights outside, int feeds exempt from
    the cast.  No other test exercised this end-to-end."""
    import numpy as np
    import hetu_tpu as ht
    from hetu_tpu import models
    from hetu_tpu.models.bert import synthetic_mlm_batch

    cfg = models.BertConfig.tiny(batch_size=4, seq_len=16, vocab_size=64,
                                 hidden_size=32, intermediate_size=64,
                                 num_hidden_layers=1,
                                 hidden_dropout_prob=0.0,
                                 attention_probs_dropout_prob=0.0)
    feeds, loss, _ = models.bert_pretrain_graph(cfg)
    opt = ht.optim.AdamOptimizer(1e-3)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     compute_dtype="bfloat16")
    ids, tt, labels, attn = synthetic_mlm_batch(cfg)
    fd = {feeds["input_ids"]: ids, feeds["token_type_ids"]: tt,
          feeds["masked_lm_labels"]: labels,
          feeds["attention_mask"]: attn}
    hist = [float(ex.run("train", feed_dict=fd)[0].asnumpy())
            for _ in range(10)]
    assert np.isfinite(hist).all() and hist[-1] < hist[0], hist
    # master copies must still be fp32 after training steps
    for n, v in ex.var_values.items():
        if n.trainable:
            assert np.asarray(v).dtype == np.float32, (n.name, v.dtype)
    # fetched loss leaves the step as fp32 (the _cast_tree discipline)
    out = ex.run("train", feed_dict=fd)[0].asnumpy()
    assert out.dtype == np.float32


@pytest.mark.slow     # 12s at HEAD (ISSUE 12 tier-1 budget);
# checkpoint resume stays via the native-format chaos/autosave tests
def test_orbax_checkpoint_bitwise_resume(tmp_path):
    """save_orbax/load_orbax round-trip: a fresh executor restored from
    the orbax tree continues bitwise (params by name, Adam state by
    ordinal, step counter) — the JAX-ecosystem-standard alternative to
    the native streamed-npy format."""
    import numpy as np
    import pytest
    pytest.importorskip("orbax.checkpoint")
    import hetu_tpu as ht
    from hetu_tpu import models
    from hetu_tpu.models.bert import synthetic_mlm_batch

    cfg = models.BertConfig.tiny(batch_size=2, seq_len=8, vocab_size=32,
                                 hidden_size=16, intermediate_size=32,
                                 num_hidden_layers=1,
                                 hidden_dropout_prob=0.0,
                                 attention_probs_dropout_prob=0.0)
    feeds, loss, _ = models.bert_pretrain_graph(cfg)
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(1e-3).minimize(loss)]},
        seed=0)
    ids, tt, labels, attn = synthetic_mlm_batch(cfg)
    fd = {feeds["input_ids"]: ids, feeds["token_type_ids"]: tt,
          feeds["masked_lm_labels"]: labels,
          feeds["attention_mask"]: attn}
    for _ in range(3):
        ex.run("train", feed_dict=fd)
    ckpt = str(tmp_path / "orbax_ckpt")
    ex.save_orbax(ckpt)
    cont = [float(ex.run("train", feed_dict=fd)[0].asnumpy())
            for _ in range(3)]

    feeds2, loss2, _ = models.bert_pretrain_graph(cfg, name="bert")
    ex2 = ht.Executor(
        {"train": [loss2, ht.optim.AdamOptimizer(1e-3).minimize(loss2)]},
        seed=0)
    ex2.load_orbax(ckpt)
    assert ex2.step_counter == 3
    fd2 = {feeds2["input_ids"]: ids, feeds2["token_type_ids"]: tt,
           feeds2["masked_lm_labels"]: labels,
           feeds2["attention_mask"]: attn}
    resumed = [float(ex2.run("train", feed_dict=fd2)[0].asnumpy())
               for _ in range(3)]
    assert cont == resumed

    # warm-start form: params only, optimizer/step stay fresh
    ex3 = ht.Executor(
        {"train": [loss2, ht.optim.AdamOptimizer(1e-3).minimize(loss2)]},
        seed=0)
    ex3.load_orbax(ckpt, params_only=True)
    assert ex3.step_counter == 0


def test_manual_save_is_atomic_with_manifest(tmp_path):
    """ISSUE 2 satellite: save assembles in <path>.saving and publishes by
    rename with a size manifest in meta.json — leftovers of a preempted
    save are cleaned, overwrite keeps the old checkpoint valid until the
    new one is complete, and truncation is detectable."""
    import json
    import os
    from hetu_tpu.graph.executor import Executor

    x, y_, loss, logits, _ = _mlp_graph()
    opt = ht.optim.AdamOptimizer(0.01).minimize(loss)
    ex = ht.Executor({"train": [loss, opt]}, seed=0)
    xv, yv = _data()
    ex.run("train", feed_dict={x: xv, y_: yv})

    p = str(tmp_path / "ck")
    # leftover work dir from a preempted earlier save must not break it
    os.makedirs(p + ".saving")
    open(os.path.join(p + ".saving", "junk"), "w").close()
    ex.save(p)
    assert not os.path.exists(p + ".saving")
    assert Executor._checkpoint_complete(p)
    with open(os.path.join(p, "meta.json")) as f:
        meta = json.load(f)
    assert meta["manifest"], "manifest missing"
    for rel, size in meta["manifest"].items():
        assert os.path.getsize(os.path.join(p, rel)) == size, rel

    # overwrite in place: a second save over the same path publishes the
    # newer step atomically
    ex.run("train", feed_dict={x: xv, y_: yv})
    ex.save(p)
    with open(os.path.join(p, "meta.json")) as f:
        assert json.load(f)["step"] == 2
    assert not os.path.exists(p + ".replaced")

    # truncation (preemption mid-write of a tensor) is detected
    rel = sorted(meta["manifest"])[0]
    with open(os.path.join(p, rel), "r+b") as f:
        f.truncate(3)
    assert not Executor._checkpoint_complete(p)

    # legacy single-file blob path stays atomic too (tmp + replace)
    ex.save(str(tmp_path / "legacy"), file="blob.hetu")
    assert os.path.exists(str(tmp_path / "legacy" / "blob.hetu"))
    assert not os.path.exists(str(tmp_path / "legacy" / "blob.hetu.tmp"))
