"""BERT's MLM head on the labelled rows (ISSUE 47): the gathered head
against the all-position head — the same graph built with
``max_predictions_per_seq = seq_len``, which gathers nothing — on the
CPU in float32 at a toy size."""
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import models
from hetu_tpu.profiler import HetuProfiler

B, S, V, K, H = 4, 32, 64, 8, 48     # B·K is no other axis


def _cfg(**kw):
    return models.BertConfig.tiny(
        batch_size=B, seq_len=S, vocab_size=V, hidden_size=H,
        intermediate_size=96, num_hidden_layers=1, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, **kw)


def _labels(counts, ids, at=None):
    """Row r labels ``counts[r]`` positions (``at[r]`` where given, else
    spread over the row) with the token there."""
    labels = np.full((B, S), -1, np.int32)
    for r, n in enumerate(counts):
        pos = at[r] if at and at[r] is not None \
            else np.linspace(1, S - 2, n).astype(int)
        labels[r, pos] = ids[r, pos]
    return labels


def _feed(feeds, labels, ids, nsp=None):
    fd = {feeds["input_ids"]: ids,
          feeds["token_type_ids"]: np.zeros((B, S), np.int32),
          feeds["masked_lm_labels"]: labels,
          feeds["attention_mask"]: np.ones((B, S), np.int32)}
    if nsp is not None:
        fd[feeds["next_sentence_label"]] = nsp
    return fd


def _build(cfg, use_nsp=False, weights=None, **kw):
    """(executor, feeds, logits node, trainable variables by name): one
    ``grads`` subgraph fetching the loss, the overflow count and every
    parameter's gradient."""
    feeds, loss, logits = models.bert_pretrain_graph(cfg, use_nsp=use_nsp)
    from hetu_tpu.graph.node import PlaceholderOp, topo_sort
    params = [n for n in topo_sort([loss])
              if isinstance(n, PlaceholderOp) and n.is_variable
              and n.trainable]
    ex = ht.Executor(
        {"grads": [loss, loss.mlm_overflow] + ht.gradients(loss, params),
         "logits": [loss, logits]}, seed=3, **kw)
    if weights is not None:
        ex.load_dict(weights)
    return ex, feeds, {ex.var_names[p]: i for i, p in enumerate(params)}


def _loss_and_grads(ex, names, fd):
    out = [np.asarray(o.asnumpy()) for o in ex.run("grads", feed_dict=fd)]
    return float(out[0]), int(out[1]), {n: out[2 + i]
                                        for n, i in names.items()}


CASES = {
    # counts a row, positions (None: spread), rows over capacity
    "exactly_k": ([K, K, K, K], None, 0),
    "fewer": ([K, 3, 1, 5], None, 0),
    "a_row_with_none": ([K, 0, 2, 0], None, 0),
    "first_and_last_position": ([2, 2, 3, 1],
                                [[0, S - 1], [0, S - 1], [0, 5, S - 1],
                                 [S - 1]], 0),
    "one_row_over": ([K + 1, 2, K, 0], None, 1),
    "every_position": ([S, S, S, S], [np.arange(S)] * 4, 4),
}


@pytest.mark.parametrize("use_nsp", [False, True], ids=["mlm", "mlm_nsp"])
@pytest.mark.parametrize("case", list(CASES))
def test_gathered_head_equals_all_position_head(case, use_nsp):
    """Loss and EVERY parameter's gradient to 1e-5 relative, however many
    rounds the labels ask for (``one_row_over`` two, ``every_position``
    four); ``mlm_overflow`` counts the rows over capacity."""
    counts, at, over = CASES[case]
    rng = np.random.RandomState(5)
    ids = rng.randint(1, V, (B, S)).astype(np.int32)
    labels = _labels(counts, ids, at)
    nsp = (ids[:, 0] % 2).astype(np.int32) if use_nsp else None
    assert _cfg().max_predictions_per_seq == K
    ex, feeds, names = _build(_cfg(), use_nsp)
    ref, rfeeds, rnames = _build(_cfg(max_predictions_per_seq=S), use_nsp,
                                 weights=ex.return_tensor_values())
    assert set(names) == set(rnames)
    loss, overflow, grads = _loss_and_grads(
        ex, names, _feed(feeds, labels, ids, nsp))
    want, zero, wgrads = _loss_and_grads(
        ref, rnames, _feed(rfeeds, labels, ids, nsp))
    assert (overflow, zero) == (over, 0)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    # a key bias moves no softmax: its gradient is rounding noise, held
    # against the largest gradient and not against itself
    floor = 1e-3 * max(float(np.abs(g).max()) for g in wgrads.values())
    for name, g in wgrads.items():
        scale = max(float(np.abs(g).max()), floor)
        np.testing.assert_allclose(grads[name] / scale, g / scale,
                                   atol=1e-5, err_msg=name)
    if sum(counts):
        assert np.abs(grads["bert.mlm_decoder.weight"]).max() > 0
        assert np.abs(grads["bert.embeddings.word.weight"]).max() > 0


def _sub_jaxprs(jaxpr):
    import jax
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for j in v if isinstance(v, (tuple, list)) else (v,):
                j = getattr(j, "jaxpr", j)
                if isinstance(j, jax.extend.core.Jaxpr):
                    yield from _sub_jaxprs(j)


def _shapes(jaxpr):
    return {tuple(v.aval.shape) for eqn in _sub_jaxprs(jaxpr)
            for v in eqn.outvars}


def test_training_step_holds_no_all_position_logits():
    """The training step computes ``(B·K, vocab)`` tensors inside its one
    ``while`` and ``(B·S, vocab)`` nowhere; what crosses the loop — a
    residual would — has no vocabulary axis beside a row axis."""
    import jax
    feeds, loss, _ = models.bert_pretrain_graph(_cfg())
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(1e-3).minimize(loss)]},
        seed=0)
    fn, args = ex.export_step("train")
    step = jax.make_jaxpr(fn)(*args).jaxpr
    loops = [e for e in _sub_jaxprs(step) if e.primitive.name == "while"]
    assert len(loops) == 1
    assert (B * K, V) in _shapes(loops[0].params["body_jaxpr"].jaxpr)
    assert (B * K, V) in _shapes(step) and (B * S, V) not in _shapes(step)
    crossing = {tuple(v.aval.shape) for v in loops[0].outvars}
    assert (H, V) in crossing and (B * K, V) not in crossing


def test_head_operations_carry_the_scope_a_reader_matches():
    """``benchmarks/trace_scopes.py`` gives an operation to a scope that is
    a whole component of its framework name.  A gradient taken around a
    node renames the node's scope ``jvp(mlm_head)``; the op opens it once
    more inside, so the head's products, forward and backward, read
    ``…/mlm_head/…``."""
    import re
    rng = np.random.RandomState(9)
    ids = rng.randint(1, V, (B, S)).astype(np.int32)
    ex, feeds, _ = _build(_cfg())
    text = HetuProfiler(ex, name="grads").hlo_text(
        _feed(feeds, _labels([K, 3, 0, 5], ids), ids))
    owned = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if re.search(r"(^|/)mlm_head(/|$)", n)]
    assert [n for n in owned if n.endswith("/dot_general")
            and "transpose(jvp(" in n]          # a backward product
    assert [n for n in owned if n.endswith("/dot_general")
            and "transpose" not in n]           # a forward one


def test_logits_node_is_the_all_position_head():
    """The third return value: ``(B·S, vocab)``, from the head's own three
    layers — the loss computed from it by hand is the loss the graph
    gives."""
    rng = np.random.RandomState(6)
    ids = rng.randint(1, V, (B, S)).astype(np.int32)
    labels = _labels([K, 3, 0, 5], ids)
    ex, feeds, _ = _build(_cfg())
    loss, logits = (np.asarray(o.asnumpy()) for o in ex.run(
        "logits", feed_dict=_feed(feeds, labels, ids)))
    assert logits.shape == (B * S, V)
    flat = labels.reshape(-1)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    picked = -logp[np.arange(B * S), np.maximum(flat, 0)][flat >= 0]
    np.testing.assert_allclose(loss, picked.mean(), rtol=1e-5)


def test_head_variables_keep_their_names():
    """A checkpoint written before the head gathered loads: the same
    variables under the same names, the head's six among them."""
    ex, _, names = _build(_cfg())
    head = {f"bert.mlm_{layer}.{leaf}"
            for layer, leaves in (("transform", ("weight", "bias")),
                                  ("ln", ("scale", "bias")),
                                  ("decoder", ("weight", "bias")))
            for leaf in leaves}
    assert head <= set(names)
    assert not [n for n in names if "mlm" in n and n not in head]
    values = ex.return_tensor_values()
    assert values["bert.mlm_decoder.weight"].shape == (H, V)
    ex.load_dict({n: np.zeros_like(v) for n, v in values.items()})
    assert not ex.return_tensor_values()["bert.mlm_transform.weight"].any()


def test_mlm_head_calls_name_the_rows_a_program_runs():
    from hetu_tpu import metrics
    from hetu_tpu.graph import step_cache
    rng = np.random.RandomState(7)
    ids = rng.randint(1, V, (B, S)).astype(np.int32)
    labels = _labels([K, 1, 0, 2], ids)

    def traced(cfg):
        step_cache.clear()      # a cached step is not traced again
        before = metrics.mlm_head_call_counts()
        ex, feeds, names = _build(cfg)
        _loss_and_grads(ex, names, _feed(feeds, labels, ids))
        after = HetuProfiler.mlm_head_calls()
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)}

    assert traced(_cfg()) == {f"{K}of{S}:gathered": 1}
    assert traced(_cfg(max_predictions_per_seq=S)) == {f"{S}of{S}:all": 1}
    assert HetuProfiler.all_counters()["mlm_head_calls"] \
        == metrics.mlm_head_call_counts()


def test_default_capacity_is_the_published_one():
    """15 % of the sequence rounded up to a multiple of 8 (the published
    80 at 512; 20 at 128 becomes 24), never more than the sequence."""
    got = {s: models.BertConfig(seq_len=s).max_predictions_per_seq
           for s in (8, 16, 128, 512)}
    assert got == {8: 8, 16: 8, 128: 24, 512: 80}
    assert models.BertConfig(seq_len=128, max_predictions_per_seq=20) \
        .max_predictions_per_seq == 20
    from hetu_tpu.models.bert import synthetic_mlm_batch
    cfg = models.BertConfig.tiny(batch_size=64, seq_len=128)
    labels = synthetic_mlm_batch(cfg, seed=1)[2]
    assert 0 < (labels != -1).sum(1).max() <= 24


@pytest.mark.parametrize("axes", [{"dp": 2}, {"dp": 2, "cp": 2}],
                         ids=["dp2", "dp2_cp2"])
def test_head_under_a_mesh_gives_the_single_device_loss(axes):
    """Batch rows over ``dp`` (the per-row gather keeps them there) and,
    with ``cp``, whatever GSPMD makes of a second axis: the loss and the
    decoder's gradient of one device."""
    import jax
    import math
    rng = np.random.RandomState(8)
    ids = rng.randint(1, V, (B, S)).astype(np.int32)
    labels = _labels([K, 3, 0, 5], ids)
    ex, feeds, names = _build(_cfg())
    want, _, wgrads = _loss_and_grads(ex, names, _feed(feeds, labels, ids))
    n = math.prod(axes.values())
    if len(axes) == 1:
        kw = dict(dist_strategy=ht.dist.DataParallel(num_devices=n))
    else:
        kw = dict(mesh=ht.make_mesh(axes, jax.devices()[:n]),
                  dist_strategy=ht.dist.ModelParallel(axes))
    mex, mfeeds, mnames = _build(_cfg(), weights=ex.return_tensor_values(),
                                 **kw)
    loss, overflow, grads = _loss_and_grads(
        mex, mnames, _feed(mfeeds, labels, ids))
    assert overflow == 0
    np.testing.assert_allclose(loss, want, rtol=2e-4)
    g, w = grads["bert.mlm_decoder.weight"], wgrads["bert.mlm_decoder.weight"]
    np.testing.assert_allclose(g, w, atol=2e-4 * np.abs(w).max())
