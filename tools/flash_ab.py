"""On-chip A/B: Pallas flash attention vs XLA-composed attention.

Round-2 verdict: the flash dispatch gate (``_FLASH_MIN_LEN``) was a guess,
so there was no evidence the kernel beats XLA at any length — and the
flagship BERT bench (seq=128) never reached it.  This microbench times
fwd+bwd of both paths at BERT-base head geometry across sequence lengths
and writes the winner table to ``artifacts/flash_ab.json`` for the reader:
the program reads nothing from it (the gate is the constant
``ops/attention.py:_FLASH_MIN_LEN``; a table that disagrees with it is the
evidence for changing the constant).  A chip tool: run it on the machine
with the chip.

The flash side is timed as the dispatcher runs it: with the blocks the
kernel module's rule picks from the call's shapes (``_pick_blocks``), in
BOTH operand layouts — ``flash_ms`` the (B, H, S, D) entry alone,
``packed_ms`` the (B, S, H·D) entry ``MultiHeadAttention`` takes since
PR 44, and ``layer_ms`` the head-major entry as a layer had to call it
until then: from packed projections, a transpose each side.
``packed_max_diff`` is the largest difference, output and three gradients,
between the two entries at EQUAL blocks (0.0: the same arithmetic).
Beside them each row keeps a SWEEP of the kept geometry for the reader of
PERF.md, not for the program (nothing reads block shapes from the
artifact): forward-only and forward + backward milliseconds of each layout
at every whole-key-range block, the rule's among them, so its choice can
be seen against its neighbours (128 × 128 and square tiles lost in PR 28
and are not timed any more; nor is there a killed sweep to resume: the
chip tool brings back ``chiprun_out/`` alone).  ``--seqs 512 --tags kmask`` narrows a run.
"""
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HEADS, HEAD_DIM = 12, 64        # BERT-base geometry
TOKEN_BUDGET = 16384            # per-step tokens, constant across seqs
SEQS = (128, 256, 512, 1024)
REPS, INNER = 3, 10


def _timed_grad_step(fn, q, k, v, grad=True):
    """Best-of-REPS time for INNER fwd+bwd steps of ``fn`` (forward only
    with ``grad=False``)."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32))

    @jax.jit
    def step(q, k, v):
        if not grad:
            return loss(q, k, v)
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l + sum(jnp.sum(g.astype(jnp.float32)) for g in grads)

    jax.block_until_ready(step(q, k, v))        # compile + warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        s = 0.0
        for _ in range(INNER):
            s = step(q, k, v)
        jax.block_until_ready(s)
        best = min(best, (time.perf_counter() - t0) / INNER)
    return best * 1e3           # ms


def _sweep_blocks(seq):
    """Block shapes worth seeing beside the rule's: the whole key range
    under every query block (the one-pass backward; under ``causal`` those
    prune nothing and still won, PR 28)."""
    # a 1024 × 1024 f32 score tile is 4 MiB a temporary: past VMEM
    return [(b, seq) for b in (128, 256, 512, 1024)
            if b <= seq and seq % b == 0 and b * seq <= 512 * 1024]


def _pack(x):
    """(B, H, S, D) → (B, S, H·D), the layout a projection leaves."""
    from hetu_tpu.ops.attention import _merge_heads
    return _merge_heads(x)


def _as_layer(fn):
    """The head-major entry ``fn`` as a layer called it before PR 44:
    packed operands in, packed result out, a transpose each side."""
    from hetu_tpu.ops.attention import _head_major
    return lambda q, k, v: _head_major(fn, None, q, k, v, None, HEAD_DIM)


def _max_diff(fn_a, fn_b, args_a, args_b):
    """Largest |difference| over output and gradients of two entries,
    ``fn_b``'s compared in ``fn_a``'s layout."""
    import jax
    import jax.numpy as jnp

    def both(fn, args):
        loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)  # noqa
        return (fn(*args),) + jax.grad(loss, argnums=(0, 1, 2))(*args)
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - _pack(b).astype(jnp.float32))))
               for a, b in zip(both(fn_a, args_a), both(fn_b, args_b)))


def main(argv=None):
    import argparse
    import importlib

    import jax
    import jax.numpy as jnp

    from hetu_tpu.ops.attention import sdpa_reference
    # the package re-exports the function under the module's name
    fa = importlib.import_module("hetu_tpu.ops.pallas.flash_attention")
    flash_attention = fa.flash_attention

    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="*", default=list(SEQS))
    ap.add_argument("--tags", nargs="*",
                    default=["dense", "causal", "kmask"])
    args = ap.parse_args(argv)
    narrowed = tuple(args.seqs) != SEQS or len(args.tags) != 3

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"refusing flash A/B on the {backend} backend",
              file=sys.stderr)
        return 1
    rows = {}
    for seq in args.seqs:
        b = max(1, TOKEN_BUDGET // seq)
        key = jax.random.PRNGKey(seq)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (b, HEADS, seq, HEAD_DIM)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        packed = tuple(_pack(x) for x in (q, k, v))
        row = {"batch": b}
        # padded-pretraining key mask (the FLAGSHIP bench path since round
        # 4): same length distribution as synthetic_mlm_batch
        import numpy as np
        lrng = np.random.RandomState(seq)
        lengths = np.full((b,), seq)
        short = lrng.rand(b) >= 0.35
        lengths[short] = lrng.randint(max(1, seq // 4), seq + 1, short.sum())
        km = jnp.asarray(np.arange(seq)[None, :] < lengths[:, None])
        cases = [("dense", {}), ("causal", {"causal": True}),
                 ("kmask", {"key_mask": km})]
        for tag, kw in cases:
            if tag not in args.tags:
                continue
            causal = kw.get("causal", False)

            def flash(**at):
                return functools.partial(flash_attention, **kw, **at)

            # what the dispatcher runs: the rule's blocks — head-major,
            # the same call packed, and head-major as a layer paid for it
            fl = _timed_grad_step(flash(), q, k, v)
            row[f"flash_ms_{tag}"] = round(fl, 3)
            rule = fa._pick_blocks(seq, seq, HEAD_DIM, q.dtype.itemsize,
                                   causal)
            row[f"rule_{tag}"] = "%dx%d" % rule
            row[f"packed_ms_{tag}"] = round(
                _timed_grad_step(flash(heads=HEADS), *packed), 3)
            row[f"packed_rule_{tag}"] = "%dx%d" % fa._pick_blocks(
                seq, seq, fa.packed_width(HEAD_DIM), q.dtype.itemsize,
                causal)
            row[f"layer_ms_{tag}"] = round(
                _timed_grad_step(_as_layer(flash()), *packed), 3)
            at = dict(block_q=rule[0], block_k=rule[1])
            row[f"packed_max_diff_{tag}"] = _max_diff(
                flash(heads=HEADS, **at), flash(**at), packed, (q, k, v))
            # [fwd ms, fwd+bwd ms] head-major, then the same packed
            row[f"sweep_{tag}"] = {
                f"{bq}x{bk}": [
                    round(_timed_grad_step(
                        flash(block_q=bq, block_k=bk, **lay), *xs,
                        grad=g), 3)
                    for lay, xs in (({}, (q, k, v)),
                                    ({"heads": HEADS}, packed))
                    for g in (False, True)]
                for bq, bk in _sweep_blocks(seq)}
            ref_kw = dict(causal=causal)
            if "key_mask" in kw:
                ref_kw["mask"] = km[:, None, None, :]
            ref = functools.partial(sdpa_reference, **ref_kw)
            xl = _timed_grad_step(ref, q, k, v)
            row[f"xla_ms_{tag}"] = round(xl, 3)
            row[f"winner_{tag}"] = "flash" if fl < xl else "xla"
        rows[str(seq)] = row
        print(f"seq {seq}: {json.dumps(row)}", flush=True)

    if narrowed:
        # a narrowed run is a reading for PERF.md, not a gate: the gate's
        # rule needs every length and both flagship cases
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "flash_ab_rows.json"),
                  "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
        return 0
    out = _persist(backend, rows, partial=False)
    print(json.dumps({"flash_min_len": out["flash_min_len"]}))
    return 0


def _persist(backend, rows, partial):
    """Write the artifact (atomically)."""
    import jax

    measured = [s for s in SEQS if str(s) in rows]
    # gate rule: the smallest seq from which flash wins BOTH the dense AND
    # the key-mask case at every measured length >= it (kmask is the
    # flagship padded-pretraining path; dense the generic one).
    def _wins(s):
        row = rows[str(s)]
        # an absent kmask measurement is NOT a win — the flagship path
        # must be measured before the gate can claim flash wins it
        return row["winner_dense"] == "flash" \
            and row.get("winner_kmask") == "flash"
    flash_min_len = None
    for i, seq in enumerate(measured):
        if all(_wins(s) for s in measured[i:]):
            flash_min_len = seq
            break
    from artifact_schema import provenance

    out = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        # heads/head_dim/token_budget stay top-level (readers compare
        # geometries by them); provenance embeds only sha + hash
        "heads": HEADS, "head_dim": HEAD_DIM,
        "token_budget": TOKEN_BUDGET,
        **provenance({"heads": HEADS, "head_dim": HEAD_DIM,
                      "token_budget": TOKEN_BUDGET}, embed_workload=False),
        "rows": rows,
        "partial": partial,
        # never-wins sentinel: gate above the largest measured length
        "flash_min_len": flash_min_len if flash_min_len is not None
        else SEQS[-1] * 2,
    }
    os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
    path = os.path.join(ROOT, "artifacts", "flash_ab.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:   # atomic: a killed child can't truncate it
        json.dump(out, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return out


if __name__ == "__main__":
    sys.exit(main())
