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
kernel module's rule picks from the call's shapes (``_pick_blocks``).
Beside it each row keeps a SWEEP for the reader of PERF.md, not for the
program (nothing reads block shapes from the artifact): forward-only and
forward + backward milliseconds at yesterday's 128 × 128, at square tiles
and at whole-key-range blocks, so the rule's choice can be seen against
its neighbours.  ``--seqs 512 --tags kmask`` narrows a run.
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
    """Block shapes worth seeing beside the rule's: 128 × 128 (every call's
    blocks until PR 28), square tiles, and whole-key-range blocks (under
    ``causal`` those prune nothing but run the one-pass backward)."""
    sizes = [b for b in (128, 256, 512, 1024) if b <= seq and seq % b == 0]
    cands = {(b, b) for b in sizes} | {(b, seq) for b in sizes}
    # a 1024 × 1024 f32 score tile is 4 MiB a temporary: past VMEM
    return sorted(c for c in cands if c[0] * c[1] <= 512 * 1024)


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
    rows = {} if narrowed else _load_previous_rows(backend)
    for seq in args.seqs:
        if str(seq) in rows:
            print(f"seq {seq}: already measured (resumed)", flush=True)
            continue
        b = max(1, TOKEN_BUDGET // seq)
        key = jax.random.PRNGKey(seq)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (b, HEADS, seq, HEAD_DIM)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
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
            # what the dispatcher runs: the rule's blocks
            fl = _timed_grad_step(
                functools.partial(flash_attention, **kw), q, k, v)
            row[f"flash_ms_{tag}"] = round(fl, 3)
            row[f"flash_fwd_ms_{tag}"] = round(_timed_grad_step(
                functools.partial(flash_attention, **kw), q, k, v,
                grad=False), 3)
            row[f"rule_{tag}"] = "%dx%d" % fa._pick_blocks(
                seq, seq, HEAD_DIM, q.dtype.itemsize, causal)
            sweep = {}
            for bq, bk in _sweep_blocks(seq):
                fn = functools.partial(flash_attention, block_q=bq,
                                       block_k=bk, **kw)
                sweep[f"{bq}x{bk}"] = [
                    round(_timed_grad_step(fn, q, k, v, grad=False), 3),
                    round(_timed_grad_step(fn, q, k, v), 3)]
            row[f"sweep_{tag}"] = sweep         # [fwd ms, fwd+bwd ms]
            ref_kw = dict(causal=causal)
            if "key_mask" in kw:
                ref_kw["mask"] = km[:, None, None, :]
            ref = functools.partial(sdpa_reference, **ref_kw)
            xl = _timed_grad_step(ref, q, k, v)
            row[f"xla_ms_{tag}"] = round(xl, 3)
            row[f"xla_fwd_ms_{tag}"] = round(
                _timed_grad_step(ref, q, k, v, grad=False), 3)
            row[f"winner_{tag}"] = "flash" if fl < xl else "xla"
        rows[str(seq)] = row
        print(f"seq {seq}: {json.dumps(row)}", flush=True)
        if not narrowed:
            _persist(backend, rows, partial=True)  # completion marked below

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


def _load_previous_rows(backend):
    """Rows measured by an earlier KILLED sweep (partial=true) on the SAME
    backend and measurement geometry — restarting from scratch would
    re-lose them at the first persist.  Complete artifacts are never
    resumed (a manual rerun means the caller wants fresh numbers), rows
    from a different geometry or from a pre-kmask tool version (no
    winner_kmask) are dropped so they get re-measured rather than
    vacuously satisfying the both-must-win gate."""
    path = os.path.join(ROOT, "artifacts", "flash_ab.json")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if data.get("backend") != backend or not data.get("partial"):
        return {}
    if (data.get("heads"), data.get("head_dim"),
            data.get("token_budget")) != (HEADS, HEAD_DIM, TOKEN_BUDGET):
        return {}
    return {seq: row for seq, row in data.get("rows", {}).items()
            if "winner_kmask" in row}


def _persist(backend, rows, partial):
    """Write the artifact after EVERY measured seq (atomic): a run killed
    mid-sweep must not lose the rows already measured."""
    import jax

    measured = [s for s in SEQS if str(s) in rows]
    # gate rule: the smallest seq from which flash wins BOTH the dense AND
    # the key-mask case at every measured length >= it (kmask is the
    # flagship padded-pretraining path; dense the generic one).  Partial
    # artifacts carry a prefix-only gate: read it only once partial=false.
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
        # heads/head_dim/token_budget stay top-level (the resume check
        # reads them); provenance embeds only sha + hash over them
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
