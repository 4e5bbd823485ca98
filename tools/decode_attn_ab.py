"""On-chip A/B of the one-token attention over a KV slab.

Times ``ops/pallas/decode_attention.py`` at the shapes its four callers
compile in the benchmark's serving cells — the latent read of
``glm47-flash.think-c128`` ((128, 1, 4096, 640) bfloat16, 20 score rows,
the value the first 512 lanes of the key), the shared-KV readers of
``phi4-mini-flash.reason-c64`` ((64, 10, 4608, 128) bfloat16 paired rows,
four score rows a key pair), GPT-2's packed heads in
``gpt2-medium.chat-c16`` ((16, 16, 384, 128) float32, two score rows a
head) and the grouped-query read of ``solar-open2.assist-c128`` ((128, 1,
4096, 128) bfloat16, 8 score rows) — with lengths drawn from each cell's
own table (a slot holds request ``i`` for a time proportional to its
prompt + output and sits at a uniform depth of it), against the whole-slab
``jnp`` read, and sweeps the geometry (heads per program x slab rows per
key block) beside the one ``decode_attention.geometry`` picks.  At the
picked geometry it also reads the kernel's two halves alone: ``copy`` (the
blocks fetched, no product taken) and ``products`` (the products over
buffers nothing was copied into).  For the reader of PERF.md: the program
reads nothing from what this prints.  A chip tool: run it on the machine
with the chip.

``--chunk C`` (PR 46) times the kernel's CHUNK form — ``C`` positions a
sequence, ``C x pack`` score rows a head, each with its own causal limit,
what ``dispatch_sdpa_prefill`` calls for a chunked step — at the float32
cells' shapes (``--cells chat,docqa``; ``docqa``: ``gpt2-medium.docqa-c16``,
(16, 16, 512, 128)) against ``sdpa_slab_reference`` over the whole slab, a
sequence's rows ending at a length drawn as above (at least ``C``).  There
every one of the ``READERS`` calls has slabs of its OWN, as a step's 24
layers have.

Every timed program makes ``READERS`` calls over the SAME slabs, as the
phi4 step's eight readers do; the compiler merges the ``jnp`` calls' products
over a float32 slab into one read of it, so the chat cell's ``jnp_whole``
is an eighth of one read and says nothing about a layer of that cell.
Lengths ``full`` price the slab read whole, ``one`` a grid step (one
block a slot).
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

READERS = 8          # calls a timed program makes (the phi4 step has eight)
REPS, INNER = 3, 10

CELLS = {
    "glm": {"traffic": "think-c128", "slab": (128, 1, 4096, 640),
            "dtype": "bfloat16", "rows": 20, "pack": 1, "v_lanes": 512,
            "sweep": [(1, 2048), (1, 1024), (1, 512), (1, 4096)]},
    "phi4": {"traffic": "reason-c64", "slab": (64, 10, 4608, 128),
             "dtype": "bfloat16", "rows": 4, "pack": 1,
             "sweep": [(10, 512), (10, 256), (10, 128), (10, 1152),
                       (5, 512)]},
    "chat": {"traffic": "chat-c16", "slab": (16, 16, 384, 128),
             "dtype": "float32", "rows": 2, "pack": 2,
             "sweep": [(16, 128), (16, 64), (16, 32), (16, 192),
                       (16, 384)]},
    "solar": {"traffic": "assist-c128", "slab": (128, 1, 4096, 128),
              "dtype": "bfloat16", "rows": 8, "pack": 1,
              "sweep": [(1, 4096), (1, 2048), (1, 1024)]},
    "docqa": {"traffic": "docqa-c16", "slab": (16, 16, 512, 128),
              "dtype": "float32", "rows": 2, "pack": 2,
              "sweep": [(16, 128), (16, 64), (16, 32), (8, 256)]},
}


def cell_lengths(traffic, slots, seed):
    """Key rows each of ``slots`` sequences holds at a random moment of
    the cell's steady state."""
    import numpy as np
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           traffic + ".json")) as f:
        table = np.asarray(json.load(f)["table"], np.int64)
    total = table.sum(axis=1)
    rng = np.random.default_rng(seed)
    held = rng.choice(len(table), size=slots, p=total / total.sum())
    return np.maximum(1, (rng.random(slots) * total[held]).astype(np.int32))


def _time(fn, *args):
    import jax
    step = jax.jit(fn)
    jax.block_until_ready(step(*args))              # compile + warm
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = None
        for _ in range(INNER):
            out = step(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / INNER)
    return best * 1e3 / READERS                     # ms a call


def _readers(call):
    """``READERS`` calls in one program, each with its own queries."""
    def run(rows, k, v, lengths):
        return sum(call(rows * (1.0 + 0.01 * i), k, v, lengths)
                   for i in range(READERS))
    return run


def _readers_own(call):
    """``READERS`` calls in one program, each over slabs of its own."""
    def run(q, ks, vs, lengths):
        return sum(call(q * (1.0 + 0.01 * i), k, v, lengths)
                   for i, (k, v) in enumerate(zip(ks, vs)))
    return run


def _jnp_whole(pack, v_lanes):
    """The whole-slab read the ``jnp`` paths make: scores against every
    slab row, masked afterwards, one softmax per score row."""
    import jax
    import jax.numpy as jnp

    def call(rows, k, v, lengths):
        v = k[..., :v_lanes] if v is None else v
        s = jnp.einsum("bhrl,bhml->bhrm", rows, k,
                       preferred_element_type=jnp.float32)
        key = (jnp.arange(k.shape[2])[None, :] * pack
               + (jnp.arange(rows.shape[2]) % pack)[:, None])
        seen = key[None, None] < lengths[:, None, None, None]
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("bhrm,bhml->bhrl", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)
    return call


def _stood_in(da, **names):
    """Time under stand-ins for names of the kernel's module (its geometry
    rule, one of its halves): they are functions of the module, looked up
    when the kernel's jitted call is traced, so what was traced under
    other names is dropped before and after."""
    import jax

    def timed(call, *args, readers=_readers):
        kept = {name: getattr(da, name) for name in names}
        try:
            for name, value in names.items():
                setattr(da, name, value)
            jax.clear_caches()
            return _time(readers(call), *args)
        except Exception as e:  # noqa: BLE001 - a refused geometry is data
            return f"refused: {str(e).splitlines()[0][:120]}"
        finally:
            for name, value in kept.items():
                setattr(da, name, value)
            jax.clear_caches()
    return timed


def _block_unmasked(da):
    """A stand-in for the kernel's ``_block`` that counts every key."""
    import jax
    import jax.numpy as jnp

    def block(q, k, v, first, length, m_scr, l_scr, acc_scr, pack,
              chunk=None):
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        da._fold(s, jnp.full(s.shape, True), v, m_scr, l_scr, acc_scr)
    return block


def chunk_ab(name, chunk, seed, dev):
    """The chunk form at cell ``name``'s slab shape, ``chunk`` positions a
    sequence: one line of times a lengths mix."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    cell = CELLS[name]
    b, h, slab_rows, lanes = cell["slab"]
    pack = cell["pack"]
    d = lanes // pack
    dtype = jnp.dtype(cell["dtype"])
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)),
                            1 + 2 * READERS)
    q = jax.random.normal(keys[0], (b, h, chunk, d), jnp.float32).astype(dtype)
    ks, vs = (tuple(jax.random.normal(k, cell["slab"], jnp.float32).astype(
        dtype) for k in keys[1 + i::2]) for i in range(2))

    def kernel(q, k, v, first):
        return da.decode_attention(
            att.kv_slab_chunk_rows(q * d ** -0.5, pack), k, v, first,
            pack=pack, chunk=chunk)

    def reference(q, k, v, first):
        return att.sdpa_slab_reference(
            q, k, v, first[:, None] + jnp.arange(chunk)[None, :])

    picked = da.geometry(h, slab_rows, lanes, dtype.itemsize, 2, chunk * pack)
    ends = {"cell": np.maximum(chunk, cell_lengths(cell["traffic"], b, seed)),
            "full": np.full(b, slab_rows * pack, np.int32),
            "one": np.full(b, chunk, np.int32)}
    for mix, end in ends.items():
        first = jnp.asarray(end - chunk + 1, jnp.int32)
        out = {"cell": name, "chunk": chunk, "lengths": mix,
               "device": dev.device_kind, "mean_len": float(end.mean()),
               "picked": list(picked), "slab_keys": slab_rows * pack,
               "ms_per_call": {}}
        ms = out["ms_per_call"]
        ms["slab_reference"] = _time(_readers_own(reference), q, ks, vs,
                                     first)

        def under(**names):
            return _stood_in(da, **names)(kernel, q, ks, vs, first,
                                          readers=_readers_own)
        sweep = cell["sweep"] if mix == "cell" else cell["sweep"][:2]
        for geo in [tuple(picked)] + [g for g in sweep if g != picked]:
            ms[f"{geo[0]}x{geo[1]}"] = under(
                geometry=lambda *shape, geo=geo: geo)
        ms["copy_alone"] = under(_products=lambda *a, **kw: None)
        ms["products_alone"] = under(_fetch=lambda *a, **kw: [])
        # what the per-row limits cost: every key counted (a time only)
        ms["unmasked"] = under(_block=_block_unmasked(da))
        got, want = (jax.jit(f)(q, ks[0], vs[0], first)
                     for f in (kernel, reference))
        out["max_abs_diff"] = float(jnp.max(jnp.abs(got - want)))
        out["rows_fetched_pct"] = 100.0 * att.kv_rows_fetched(
            end, cell["slab"], pack, dtype.itemsize, chunk
        ) / (b * slab_rows * pack)
        print(json.dumps(out), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="glm,phi4,chat,solar")
    ap.add_argument("--seed", type=int, default=2860486313)
    ap.add_argument("--chunk", type=int, default=1,
                    help="positions a sequence: above 1 the chunk form")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from hetu_tpu.ops.attention import kv_rows_fetched
    from hetu_tpu.ops.pallas import decode_attention as da
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs the chip, found {dev.platform}"}))
        return 2
    for name in args.cells.split(","):
        if args.chunk > 1:
            chunk_ab(name, args.chunk, args.seed, dev)
            continue
        cell = CELLS[name]
        b, h, slab_rows, lanes = cell["slab"]
        dtype = jnp.dtype(cell["dtype"])
        pack, v_lanes = cell["pack"], cell.get("v_lanes")
        key = jax.random.PRNGKey(args.seed % (2 ** 31))
        kq, kk, kv = jax.random.split(key, 3)
        rows = jax.random.normal(kq, (b, h, cell["rows"], lanes),
                                 jnp.float32).astype(dtype)
        k = jax.random.normal(kk, cell["slab"], jnp.float32).astype(dtype)
        v = None if v_lanes else jax.random.normal(
            kv, cell["slab"], jnp.float32).astype(dtype)
        kernel = functools.partial(da.decode_attention, pack=pack,
                                   v_lanes=v_lanes)
        mixes = {"cell": cell_lengths(cell["traffic"], b, args.seed),
                 "full": np.full(b, slab_rows * pack, np.int32),
                 "one": np.ones(b, np.int32)}
        picked = da.geometry(h, slab_rows, lanes, dtype.itemsize,
                             1 if v_lanes else 2)
        for mix, lengths in mixes.items():
            n = jnp.asarray(lengths, jnp.int32)
            out = {"cell": name, "lengths": mix, "device": dev.device_kind,
                   "mean_len": float(lengths.mean()), "picked": list(picked),
                   "slab_keys": slab_rows * pack, "ms_per_call": {}}
            out["ms_per_call"]["jnp_whole"] = _time(
                _readers(_jnp_whole(pack, v_lanes)), rows, k, v, n)
            sweep = cell["sweep"] if mix == "cell" else cell["sweep"][:2]
            for geo in [tuple(picked)] + [g for g in sweep if g != picked]:
                out["ms_per_call"][f"{geo[0]}x{geo[1]}"] = _stood_in(
                    da, geometry=lambda *shape, geo=geo: geo)(
                        kernel, rows, k, v, n)
            # the picked geometry's halves: the blocks copied and nothing
            # multiplied; the products over buffers nothing was copied into
            out["ms_per_call"]["copy_alone"] = _stood_in(
                da, _products=lambda *a, **kw: None)(kernel, rows, k, v, n)
            out["ms_per_call"]["products_alone"] = _stood_in(
                da, _fetch=lambda *a, **kw: [])(
                    kernel, rows, k, v, n)
            out["rows_fetched_pct"] = 100.0 * kv_rows_fetched(
                lengths, cell["slab"], pack, dtype.itemsize
            ) / (b * slab_rows * pack)
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
