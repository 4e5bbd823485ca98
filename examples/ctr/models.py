"""CTR models over ht ops — WDL / DeepFM / DCN on Criteo-format data.

Parity with the reference ``examples/embedding/ctr/models/`` (wdl_criteo,
deepfm_criteo, dcn_criteo): 13 dense + 26 categorical fields, a shared
embedding table addressed with per-field offsets, binary cross-entropy loss.
The embedding either lives in-graph (dense variable) or host-side through
``ht.ps_embedding_lookup_op`` (+ optional HET cache) — the reference's
PS/cache path (run_hetu.py:121-126).
"""
import numpy as np

import hetu_tpu as ht

NUM_DENSE = 13
NUM_SPARSE = 26


def _embed(ids_node, vocab, dim, mode, lr, name, batch_ids=None):
    """Shared embedding: dense variable or PS/cache host table.

    Modes: ``dense`` (in-graph variable), ``ps`` (direct host store, no
    cache), ``lru``/``lfu``/``lfuopt`` (native C++ HET cache),
    ``vlru``/``vlfu`` (the vectorized numpy HET cache —
    :class:`hetu_tpu.ps.DistCacheTable` — the batched sparse-RPC path), and
    ``vlru_dev``/``vlfu_dev`` (the same cache with the DEVICE-RESIDENT
    slab: hit rows gathered on-device by slot index, only miss rows
    crossing the host boundary, grads segment-summed by the Pallas
    scatter-add kernel)."""
    if mode == "dense":
        table = ht.Variable(
            name, initializer=ht.init.GenNormal(0.0, 0.01), shape=(vocab, dim),
            trainable=True, is_embed=True)
        return ht.embedding_lookup_op(table, ids_node)
    if mode == "ps":
        store = ht.default_store()
        t = store.init_table(vocab, dim, opt="sgd", lr=lr, seed=0,
                             init_scale=0.01)
        return ht.ps_embedding_lookup_op((store, t), ids_node, width=dim)
    if mode in ("vlru", "vlfu", "vlru_dev", "vlfu_dev"):
        from hetu_tpu.ps import DistCacheTable, EmbeddingStore
        store = EmbeddingStore()
        t = store.init_table(vocab, dim, opt="sgd", lr=lr, seed=0,
                             init_scale=0.01)
        device = mode.endswith("_dev")
        # scratch bound: a batch can never hold more uncacheable unique
        # keys than its own flattened id count, so batch_ids scratch
        # rows make overflow impossible at batch-sized memory cost (the
        # vocab would also bound it — but a vocab-sized scratch would
        # dwarf the cache and defeat its memory rationale)
        scratch = min(vocab, batch_ids) if device and batch_ids \
            else (vocab if device else None)
        cache = DistCacheTable(store, t, limit=max(vocab // 10, 256),
                               pull_bound=10, push_bound=10,
                               policy=mode[1:4], device=device,
                               device_scratch=scratch)
        return ht.ps_embedding_lookup_op(cache, ids_node, width=dim)
    # native cache policies: lru / lfu / lfuopt
    cs = ht.CacheSparseTable(limit=max(vocab // 10, 256), length=vocab,
                             width=dim, policy=mode, bound=10, opt="sgd",
                             lr=lr, seed=0)
    return ht.ps_embedding_lookup_op(cs, ids_node)


def _mlp(x, dims, name):
    h = x
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        w = ht.Variable(f"{name}_w{i}",
                        initializer=ht.init.GenXavierNormal(),
                        shape=(din, dout))
        b = ht.Variable(f"{name}_b{i}", initializer=ht.init.GenZeros(),
                        shape=(dout,))
        hm = ht.matmul_op(h, w)
        h = hm + ht.broadcastto_op(b, hm)
        if i < len(dims) - 2:
            h = ht.relu_op(h)
    return h


def wdl_criteo(dense, sparse, y_, batch_size, vocab=100000, dim=16,
               embed_mode="dense", lr=0.01):
    """Wide & Deep (reference models/wdl_criteo.py)."""
    emb = _embed(sparse, vocab, dim, embed_mode, lr, "wdl_embed",
                 batch_ids=batch_size * NUM_SPARSE)
    flat = ht.array_reshape_op(emb, (batch_size, NUM_SPARSE * dim))
    deep_in = ht.concat_op(flat, dense, axis=1)
    deep = _mlp(deep_in, [NUM_SPARSE * dim + NUM_DENSE, 256, 256, 1], "deep")
    wide = _mlp(dense, [NUM_DENSE, 1], "wide")
    logit = wide + deep
    prob = ht.sigmoid_op(logit)
    loss = ht.reduce_mean_op(
        ht.binarycrossentropy_op(prob, y_), [0, 1])
    return loss, prob


def deepfm_criteo(dense, sparse, y_, batch_size, vocab=100000, dim=16,
                  embed_mode="dense", lr=0.01):
    """DeepFM (reference models/deepfm_criteo.py): FM 2nd-order term via
    0.5*((Σv)² − Σv²) + linear term + deep MLP."""
    emb = _embed(sparse, vocab, dim, embed_mode, lr, "fm_embed",
                 batch_ids=batch_size * NUM_SPARSE)  # B,26,D
    sum_vec = ht.reduce_sum_op(emb, [1])                  # B,D
    sum_sq = ht.mul_op(sum_vec, sum_vec)
    sq = ht.mul_op(emb, emb)
    sq_sum = ht.reduce_sum_op(sq, [1])
    fm2 = ht.reduce_sum_op(sum_sq - sq_sum, [1], keepdims=True) * 0.5  # B,1
    lin = _mlp(dense, [NUM_DENSE, 1], "fm_lin")
    flat = ht.array_reshape_op(emb, (batch_size, NUM_SPARSE * dim))
    deep = _mlp(flat, [NUM_SPARSE * dim, 256, 256, 1], "fm_deep")
    prob = ht.sigmoid_op(lin + fm2 + deep)
    loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0, 1])
    return loss, prob


def dcn_criteo(dense, sparse, y_, batch_size, vocab=100000, dim=16,
               embed_mode="dense", lr=0.01, n_cross=3):
    """Deep & Cross (reference models/dcn_criteo.py): x_{l+1} = x0·(x_l·w) +
    b + x_l cross layers alongside a deep tower."""
    emb = _embed(sparse, vocab, dim, embed_mode, lr, "dcn_embed",
                 batch_ids=batch_size * NUM_SPARSE)
    flat = ht.array_reshape_op(emb, (batch_size, NUM_SPARSE * dim))
    x0 = ht.concat_op(flat, dense, axis=1)
    width = NUM_SPARSE * dim + NUM_DENSE
    x = x0
    for i in range(n_cross):
        w = ht.Variable(f"cross_w{i}", initializer=ht.init.GenXavierNormal(),
                        shape=(width, 1))
        b = ht.Variable(f"cross_b{i}", initializer=ht.init.GenZeros(),
                        shape=(width,))
        xw = ht.matmul_op(x, w)                       # B,1
        x = ht.mul_op(x0, ht.broadcastto_op(xw, x0)) \
            + ht.broadcastto_op(b, x) + x
    deep = _mlp(x0, [width, 256, 256], "dcn_deep")
    both = ht.concat_op(x, deep, axis=1)
    logit = _mlp(both, [width + 256, 1], "dcn_out")
    prob = ht.sigmoid_op(logit)
    loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0, 1])
    return loss, prob


def synthetic_criteo_skewed(n_rows, vocab=100000, seed=0, zipf_a=1.1):
    """Criteo-FORMAT dataset with the two properties the real one has that
    the uniform generator lacks: heavily skewed (Zipf) id frequencies —
    which is what makes the HET cache effective (reference README ctr:33,
    HET VLDB'22) — and a click signal carried partly by the CATEGORICAL
    fields, so embedding learning moves AUC, not just the dense MLP.

    Returns (dense, sparse, y) for the whole dataset; slice into batches.
    """
    rng = np.random.RandomState(seed)
    dense = rng.rand(n_rows, NUM_DENSE).astype(np.float32)
    per_field = vocab // NUM_SPARSE
    ranks = np.arange(per_field, dtype=np.float64)
    p = 1.0 / (ranks + 1.0) ** zipf_a
    p /= p.sum()
    field = np.stack([rng.choice(per_field, n_rows, p=p)
                      for _ in range(NUM_SPARSE)], axis=1)
    offsets = np.arange(NUM_SPARSE) * per_field
    sparse = (field + offsets).astype(np.int64)
    # planted signal: dense linear part + per-id categorical effects on a
    # few fields (hash-derived so frequent ids carry consistent signal)
    cat_effect = np.cos(field[:, :6] * 2.399963).sum(axis=1)
    signal = dense @ rng.randn(NUM_DENSE) * 0.5 + 0.8 * cat_effect
    y = signal + 0.5 * rng.randn(n_rows) > np.median(signal)
    return dense, sparse, y.astype(np.float32).reshape(-1, 1)


def validate_cache_parity(steps=300, batch_size=512, vocab=100000, dim=16,
                          policy="lru", bound=10, lr=0.01, seed=0,
                          record_every=10):
    """Loss-parity validation: WDL trained through the HET cache vs the
    direct store on identical Criteo-format skewed data (BASELINE config 4;
    reference cache flags run_hetu.py:121-126).  Returns a JSON-ready dict
    with both loss curves, AUCs, divergence, and cache counters."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.ps import EmbeddingStore, CacheSparseTable

    n_rows = steps * batch_size + batch_size
    dense_all, sparse_all, y_all = synthetic_criteo_skewed(
        n_rows, vocab=vocab, seed=seed)
    table0 = np.random.RandomState(seed).normal(
        0.0, 0.01, (vocab, dim)).astype(np.float32)

    def run(use_cache):
        store = EmbeddingStore()
        t = store.init_table(vocab, dim, opt="sgd", lr=lr, seed=seed,
                             init_scale=0.01)
        store.set_data(t, table0.copy())
        if use_cache:
            cs = CacheSparseTable(limit=max(vocab // 10, 256), length=vocab,
                                  width=dim, policy=policy, bound=bound,
                                  store=store, table=t)
            embed_src = cs
        else:
            cs = None
            embed_src = (store, t)
        dense = ht.placeholder_op("dense")
        sparse = ht.placeholder_op("sparse", dtype=np.int64)
        y_ = ht.placeholder_op("y")
        emb = ht.ps_embedding_lookup_op(embed_src, sparse, width=dim)
        flat = ht.array_reshape_op(emb, (batch_size, NUM_SPARSE * dim))
        deep_in = ht.concat_op(flat, dense, axis=1)
        deep = _mlp(deep_in, [NUM_SPARSE * dim + NUM_DENSE, 256, 256, 1],
                    "deep")
        wide = _mlp(dense, [NUM_DENSE, 1], "wide")
        prob = ht.sigmoid_op(wide + deep)
        loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0, 1])
        opt = ht.optim.AdamOptimizer(lr)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)],
                          "eval": [prob]}, seed=seed)
        curve = []
        for i in range(steps):
            lo = batch_size * i
            fd = {dense: dense_all[lo:lo + batch_size],
                  sparse: sparse_all[lo:lo + batch_size],
                  y_: y_all[lo:lo + batch_size]}
            out = ex.run("train", feed_dict=fd)
            if i % record_every == 0:
                curve.append(round(float(out[0].asnumpy()), 6))
        lo = batch_size * steps      # held-out tail batch
        pv = ex.run("eval", feed_dict={
            dense: dense_all[lo:lo + batch_size],
            sparse: sparse_all[lo:lo + batch_size],
            y_: y_all[lo:lo + batch_size]},
            convert_to_numpy_ret_vals=True)[0]
        auc = float(ht.metrics.auc(pv.ravel(),
                                   y_all[lo:lo + batch_size].ravel()))
        perf = cs.perf() if cs is not None else {}
        if cs is not None:
            cs.flush()
        return curve, auc, perf

    curve_off, auc_off, _ = run(False)
    curve_on, auc_on, perf = run(True)
    diffs = [abs(a - b) for a, b in zip(curve_off, curve_on)]
    return {
        "config": {"steps": steps, "batch_size": batch_size, "vocab": vocab,
                   "dim": dim, "policy": policy, "bound": bound, "lr": lr,
                   "zipf_a": 1.1},
        "loss_curve_cache_off": curve_off,
        "loss_curve_cache_on": curve_on,
        "max_curve_divergence": round(max(diffs), 6),
        "final_divergence": round(diffs[-1], 6),
        "auc_cache_off": round(auc_off, 4),
        "auc_cache_on": round(auc_on, 4),
        "cache_perf": perf,
        # READ hit rate: read hits / read lookups (write traffic counts
        # separately since the round-4 counter split — cache.h perf_
        # semantics; the old shared counter reported hits > lookups)
        "cache_hit_rate": round(perf.get("hit_rate", 0.0), 4),
    }


def synthetic_criteo(batch_size, vocab=100000, seed=0):
    """Criteo-shaped synthetic batch: 13 float features, 26 categorical ids
    (field-offset layout like the reference's preprocessed dataset), click
    label with a planted linear signal so AUC is learnable."""
    rng = np.random.RandomState(seed)
    dense = rng.rand(batch_size, NUM_DENSE).astype(np.float32)
    per_field = vocab // NUM_SPARSE
    field = rng.randint(0, per_field, (batch_size, NUM_SPARSE))
    offsets = np.arange(NUM_SPARSE) * per_field
    sparse = (field + offsets).astype(np.int64)
    signal = dense @ rng.randn(NUM_DENSE) + 0.003 * (field[:, 0] % 37 - 18)
    y = signal + 0.3 * rng.randn(batch_size) > np.median(signal)
    return dense, sparse, y.astype(np.float32).reshape(-1, 1)
