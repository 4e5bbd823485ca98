"""The training graphs the audits compile: BERT MLM, resnet18/CIFAR10,
Wide&Deep CTR and a GShard MoE layer, each as ``(cfg, executor, feeds)``.

``tools/hlo_audit.py`` and ``tools/overlap_audit.py`` compile these;
``tests/test_obs.py`` steps the tiny BERT.  The benchmark builds its own
(``benchmarks/systems/``).
"""
import importlib.util
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_example_models(family):
    """Load ``examples/<family>``'s models under a unique module name.

    Both cnn and ctr call their module ``models``; a plain ``import
    models`` serves whichever loaded first to the second caller when one
    process builds several configs (tools/hlo_audit.py --config all)."""
    base = os.path.join(ROOT, "examples", family)
    path = os.path.join(base, "models", "__init__.py")
    if not os.path.exists(path):
        path = os.path.join(base, "models.py")
    name = f"_audit_{family}_models"
    if name in sys.modules:
        return sys.modules[name]
    kw = {}
    if path.endswith("__init__.py"):   # package: enable relative imports
        kw["submodule_search_locations"] = [os.path.dirname(path)]
    spec = importlib.util.spec_from_file_location(name, path, **kw)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        # never leave a half-initialized module for the next caller's
        # fast path to silently reuse
        sys.modules.pop(name, None)
        raise
    return mod


def build_bert_graph(batch_size=32, seq_len=512, compute_dtype=None,
                     size="base", dp=None, zero=None, remat=None):
    """BERT padded MLM Adam step.  Returns (cfg, ex, fd).

    ``dp``: build on a data-parallel mesh of that many devices;
    ``zero``: ZeRO weight-update-sharding stage on that mesh; ``size``:
    'base' | 'tiny'; ``remat``: selective-remat policy
    (``off|dots|full|offload|auto`` — ``parallel/remat.py``)."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.models.bert import (BertConfig, bert_pretrain_graph,
                                      synthetic_mlm_batch)

    cfg = getattr(BertConfig, size)(batch_size=batch_size, seq_len=seq_len)
    feeds, loss, logits = bert_pretrain_graph(cfg)
    opt = ht.optim.AdamOptimizer(1e-4)
    strategy = ht.dist.DataParallel(num_devices=dp) if dp else None
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     compute_dtype=compute_dtype,
                     dist_strategy=strategy, zero=zero, remat=remat)
    ids, tt, labels, attn = synthetic_mlm_batch(cfg)
    # ids/labels/mask stay int32 end-to-end: integer feeds are exempt from
    # the bf16 compute_dtype cast (bf16 is exact only up to 256)
    fd = {feeds["input_ids"]: jax.device_put(np.asarray(ids, np.int32)),
          feeds["token_type_ids"]: jax.device_put(np.asarray(tt, np.int32)),
          feeds["masked_lm_labels"]: jax.device_put(np.asarray(labels, np.int32)),
          feeds["attention_mask"]: jax.device_put(np.asarray(attn, np.int32))}
    return cfg, ex, fd


def build_resnet18_graph(batch_size=128, data_format="NHWC",
                         compute_dtype=None):
    """resnet18/CIFAR10 Momentum step, NHWC by default (the TPU's lane
    mapping).  Returns (None, ex, fd)."""
    import jax
    import hetu_tpu as ht
    models = _load_example_models("cnn")

    x = ht.placeholder_op("x", shape=(batch_size, 3, 32, 32))
    y_ = ht.placeholder_op("y", shape=(batch_size, 10))
    loss, y = models.resnet18(x, y_, data_format=data_format)
    ex = ht.Executor(
        {"train": [loss,
                   ht.optim.MomentumOptimizer(0.1).minimize(loss)]},
        seed=0, compute_dtype=compute_dtype)
    rng = np.random.RandomState(0)
    xv = rng.rand(batch_size, 3, 32, 32).astype(np.float32)
    yv = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch_size)]
    fd = {x: jax.device_put(xv), y_: jax.device_put(yv)}
    return None, ex, fd


def build_wdl_graph(batch_size=2048, policy="lru"):
    """Wide&Deep CTR SGD step — f32 end-to-end by design: the workload is
    embedding-lookup bound; bf16 would round 100k-row id-gradients for no
    MXU win.  Returns (None, ex, fd)."""
    import hetu_tpu as ht
    ctr = _load_example_models("ctr")

    dense = ht.placeholder_op("dense")
    # ids must stay integral: float32 is exact only below 2^24, real
    # Criteo vocabs exceed it
    sparse = ht.placeholder_op("sparse", dtype=np.int64)
    y_ = ht.placeholder_op("y")
    loss, prob = ctr.wdl_criteo(dense, sparse, y_, batch_size,
                                vocab=100000, dim=16, embed_mode=policy,
                                lr=0.01)
    opt = ht.optim.SGDOptimizer(0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
    d, s, y = ctr.synthetic_criteo(batch_size, vocab=100000)
    return None, ex, {dense: d, sparse: s, y_: y}


def build_moe_graph(batch_tokens=8192, compute_dtype=None):
    """GShard top-2 16-expert MoE Adam step.  Returns
    ({"d":..., "experts":...}, ex, fd)."""
    import jax
    import hetu_tpu as ht

    d, experts = 512, 16
    x = ht.placeholder_op("x", shape=(batch_tokens, d))
    y_ = ht.placeholder_op("y", shape=(batch_tokens, d))
    gate = ht.layers.TopKGate(d, batch_tokens, experts, k=2,
                              capacity_factor=1.25)
    moe = ht.layers.MoELayer(gate, ht.layers.Expert(experts, d, 4 * d))
    h, aux = moe(x)
    loss = ht.reduce_mean_op(ht.ops.mul_op(h - y_, h - y_), [0, 1]) \
        + aux * 0.01
    opt = ht.optim.AdamOptimizer(1e-3)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     compute_dtype=compute_dtype)
    rng = np.random.RandomState(0)
    fd = {x: jax.device_put(rng.randn(batch_tokens, d).astype(np.float32)),
          y_: jax.device_put(rng.randn(batch_tokens, d).astype(np.float32))}
    return {"d": d, "experts": experts}, ex, fd
