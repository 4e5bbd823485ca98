#!/usr/bin/env python
"""Framework self-lint: static AST analysis of hetu_tpu's own source.

The PS layer (``hetu_tpu/ps/dist_store.py``) is 2k lines of hand-rolled
concurrency and wire protocol — exactly the code where a refactor silently
introduces a lock-order inversion or a client/server opcode drift (a frame
type mirrored by the replication plane but never handled by the server).
This tool makes those invariants *checked*, not hoped for; it runs in
tier-1 via ``tests/test_lint.py`` so every future PR is gated on it.

Checks
------
1. **lock-order** (``hetu_tpu/ps/``): per class, extract every ``with
   self._*lock`` acquisition, the lexical nesting between them, and
   same-class method calls made while holding a lock (propagated to the
   locks those methods eventually acquire).  Findings: acquisition-order
   cycles (ABBA deadlocks) and re-entrant acquisition of a non-reentrant
   ``threading.Lock``.
2. **opcodes** (``hetu_tpu/ps/``): every ``OP_*`` constant (registry
   ``defop("OP_X", n)`` calls and plain literal assignments) must have a
   unique wire value, at least one client SENDER (used as a call
   argument) and at least one server DISPATCH arm (used in an ``op ==
   OP_X`` comparison) — catching a mirrored-but-unhandled frame type.
3. **metrics**: every ``record_*`` counter family in
   ``hetu_tpu/metrics.py`` must be recorded somewhere in the package,
   have a snapshot accessor, and that accessor must be surfaced by a
   ``hetu_tpu/profiler.py`` API — counters nobody can read are dead
   telemetry.
4. **style**: unused imports and placeholder-less f-strings (the ruff
   F401/F541 subset, self-implemented because the container has no ruff;
   ``pyproject.toml`` carries the equivalent ruff config for
   environments that do).

5. **concurrency** (ISSUE 14): the repo-wide concurrency verifier —
   lock-order cycles with cross-module held-call propagation,
   non-reentrant re-entry, shared-state-without-lock from discovered
   thread entrypoints, blocking-call-under-lock, and
   condition-wait-without-predicate-loop, with a justified-allowlist
   mechanism (``# lint: held-rpc-ok <reason>``).  The engine lives in
   ``hetu_tpu/analysis/concurrency.py`` (loaded by file path so the
   CLI never imports jax); ``--concurrency`` runs it alone, and it is
   part of the default ``run_all`` gate.

6. **protocol drift** (ISSUE 20): every ``OP_*`` opcode the ps/ layer
   defines must appear in the protocol model checker's message
   alphabet (``hetu_tpu/analysis/protocol.py``
   ``PS_MESSAGE_ALPHABET`` — the model gives it transition semantics)
   or in its allowlist (``PS_OPCODE_ALLOWLIST`` — an explicit reason
   why it carries no replicated-state mutation), so a new
   replication-relevant opcode cannot silently bypass the model.
   Stale alphabet entries (opcodes that no longer exist) and
   reason-less entries are findings too.

Usage: ``python tools/hetu_lint.py [--concurrency] [root]`` — prints
findings, exits non-zero if any.  Every check also takes raw source
strings so the test suite can prove each detector fires on a synthetic
violation.
"""
from __future__ import annotations

import ast
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_concurrency_mods = {}      # resolved engine path -> loaded module


def concurrency_engine(root=REPO):
    """The ISSUE 14 static concurrency verifier, loaded by FILE PATH
    (``hetu_tpu/analysis/concurrency.py`` is stdlib-only; loading it
    this way keeps the lint CLI independent of the package's jax
    imports).  Cached PER RESOLVED PATH so linting an alternate
    checkout analyzes with that checkout's engine, not a stale one."""
    path = os.path.abspath(
        os.path.join(root, "hetu_tpu", "analysis", "concurrency.py"))
    mod = _concurrency_mods.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"_hetu_lint_concurrency_{len(_concurrency_mods)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _concurrency_mods[path] = mod
    return mod


# --------------------------------------------------------------- lock order

def check_lock_order(sources):
    """``{filename: source}`` -> lock-order findings (acquisition-order
    cycles + non-reentrant re-entry).  Since ISSUE 14 this delegates to
    the repo-wide concurrency verifier's lock-graph pass
    (``hetu_tpu/analysis/concurrency.py``: lexical with-nesting +
    held-call propagation, now ACROSS modules) — one engine, no drift.
    The full detector set (shared-state, blocking-under-lock,
    wait-loops) rides :func:`run_concurrency`."""
    eng = concurrency_engine()
    model = eng.build_model(sources)
    # parse failures stay findings (an unparseable file has unanalyzed
    # locks — the pre-delegation behavior)
    return model.errors + eng.check_lock_graph(model)


# ------------------------------------------------------------------ opcodes

def _opcode_defs(tree, fname, findings):
    """{name: value} for OP_* definitions: registry defop("OP_X", n) calls
    and plain literal / range-unpack assignments."""
    defs = {}

    def add(name, value):
        if name in defs and defs[name] != value:
            findings.append(f"{fname}: opcode {name} redefined with a "
                            f"different value ({defs[name]} -> {value})")
        defs[name] = value

    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        tgt, val = node.targets[0], node.value
        if isinstance(tgt, ast.Name) and tgt.id.startswith("OP_"):
            if isinstance(val, ast.Constant) and isinstance(val.value, int):
                add(tgt.id, val.value)
            elif isinstance(val, ast.Call) and len(val.args) >= 2 \
                    and isinstance(val.args[0], ast.Constant) \
                    and isinstance(val.args[1], ast.Constant):
                # registry form: OP_X = defop("OP_X", n)
                if val.args[0].value != tgt.id:
                    findings.append(
                        f"{fname}: opcode registry name mismatch: "
                        f"{tgt.id} = defop({val.args[0].value!r}, ...)")
                add(tgt.id, int(val.args[1].value))
        elif isinstance(tgt, ast.Tuple) and all(
                isinstance(e, ast.Name) and e.id.startswith("OP_")
                for e in tgt.elts):
            # OP_A, OP_B, ... = range(lo, hi)
            if isinstance(val, ast.Call) \
                    and getattr(val.func, "id", "") == "range":
                args = [a.value for a in val.args
                        if isinstance(a, ast.Constant)]
                if len(args) == len(val.args):
                    vals = list(range(*args))
                    for e, v in zip(tgt.elts, vals):
                        add(e.id, v)
    return defs


def check_opcodes(sources):
    """``{filename: source}`` -> findings: duplicate wire values, opcodes
    with no client sender, opcodes with no server dispatch arm."""
    findings = []
    defs = {}
    senders, dispatch = set(), set()
    for fname, src in sources.items():
        try:
            tree = ast.parse(src)
        except SyntaxError as e:
            findings.append(f"{fname}: syntax error: {e}")
            continue
        defs.update(_opcode_defs(tree, fname, findings))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Name) \
                            and arg.id.startswith("OP_"):
                        senders.add(arg.id)
            elif isinstance(node, ast.Compare):
                ops = [node.left] + list(node.comparators)
                if any(isinstance(o, ast.Eq) for o in node.ops):
                    for o in ops:
                        if isinstance(o, ast.Name) \
                                and o.id.startswith("OP_"):
                            dispatch.add(o.id)
    by_value = {}
    for name, value in sorted(defs.items()):
        if value in by_value:
            findings.append(
                f"opcode value collision: {name} and {by_value[value]} "
                f"both use wire value {value}")
        by_value.setdefault(value, name)
    for name in sorted(defs):
        if name not in senders:
            findings.append(
                f"opcode {name} has no client sender (never passed to an "
                f"RPC call) — dead or drifted protocol arm")
        if name not in dispatch:
            findings.append(
                f"opcode {name} has no server dispatch arm (never "
                f"compared with ==) — a client can send a frame the "
                f"server cannot handle")
    return findings


# ----------------------------------------------------------- protocol drift

_protocol_mods = {}      # resolved checker path -> loaded module


def protocol_checker(root=REPO):
    """The ISSUE 20 protocol model checker
    (``hetu_tpu/analysis/protocol.py``), loaded by FILE PATH with the
    same per-resolved-path cache discipline as
    :func:`concurrency_engine` — the module is stdlib-only, so the lint
    CLI stays independent of the package's jax imports."""
    path = os.path.abspath(
        os.path.join(root, "hetu_tpu", "analysis", "protocol.py"))
    mod = _protocol_mods.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"_hetu_lint_protocol_{len(_protocol_mods)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _protocol_mods[path] = mod
    return mod


def check_protocol_alphabet(sources, alphabet=None, allowlist=None,
                            root=REPO):
    """``{filename: source}`` (the ps/ tree) -> findings: every ``OP_*``
    opcode defined there must appear in the protocol model's message
    alphabet (``PS_MESSAGE_ALPHABET`` — the checker gives it transition
    semantics) or in the allowlist (``PS_OPCODE_ALLOWLIST`` — an
    explicit reason it carries no replicated-state mutation), never in
    both; and neither map may name an opcode that no longer exists or
    carry an empty reason.  ``alphabet``/``allowlist`` overrides let the
    synthetic-violation tests exercise each finding."""
    findings = []
    if alphabet is None or allowlist is None:
        mod = protocol_checker(root)
        if alphabet is None:
            alphabet = mod.PS_MESSAGE_ALPHABET
        if allowlist is None:
            allowlist = mod.PS_OPCODE_ALLOWLIST
    defs = {}
    for fname, src in sources.items():
        try:
            tree = ast.parse(src)
        except SyntaxError as e:
            findings.append(f"{fname}: syntax error: {e}")
            continue
        defs.update(_opcode_defs(tree, fname, findings))
    for name in sorted(defs):
        in_alpha, in_allow = name in alphabet, name in allowlist
        if not in_alpha and not in_allow:
            findings.append(
                f"opcode {name} is in neither the protocol model's "
                f"message alphabet (analysis/protocol.py "
                f"PS_MESSAGE_ALPHABET) nor its allowlist "
                f"(PS_OPCODE_ALLOWLIST) — give it model semantics or an "
                f"explicit out-of-model reason")
        elif in_alpha and in_allow:
            findings.append(
                f"opcode {name} appears in BOTH the protocol message "
                f"alphabet and the allowlist — modeled or exempt, pick "
                f"one")
    for name in sorted(set(alphabet) | set(allowlist)):
        if name not in defs:
            findings.append(
                f"protocol alphabet/allowlist names opcode {name} that "
                f"no ps/ source defines — stale model vocabulary")
    for map_name, mapping in (("PS_MESSAGE_ALPHABET", alphabet),
                              ("PS_OPCODE_ALLOWLIST", allowlist)):
        for name, reason in sorted(mapping.items()):
            if not str(reason).strip():
                findings.append(
                    f"{map_name}[{name!r}] carries an empty reason — the "
                    f"drift gate's whole point is the documented why")
    return findings


# ------------------------------------------------------------------ metrics

#: registry factory methods whose module-level assignments register an
#: instrument (``_x = REGISTRY.counter_family("name", ...)``)
_REGISTRY_CTORS = ("counter_family", "histogram", "gauge")


def check_metrics(metrics_src, profiler_src, usage_srcs=None):
    """Telemetry-registry coverage (ISSUE 10 extension of the counter
    self-lint).  Over metrics.py: every REGISTERED instrument (an
    ``obs.registry`` ``counter_family``/``histogram``/``gauge``
    assignment) must have a ``record_*`` recording site, every recorder
    must have a snapshot accessor that profiler.py surfaces, and every
    recorder must be CALLED somewhere in the package.  A raw
    ``collections.Counter`` family is itself a finding — it is
    invisible to ``metrics_dump()``/Prometheus (pre-registry families
    get the same recorder/accessor checks so the synthetic tests keep
    meaning).  Over the rest of the package: a ``def record_*`` outside
    metrics.py / the obs package, or a call to a ``record_*`` name
    defined in neither, is an unregistered ad-hoc recorder — counters
    nobody can dump are dead telemetry."""
    findings = []
    try:
        mtree = ast.parse(metrics_src)
    except SyntaxError as e:
        return [f"metrics.py: syntax error: {e}"]
    counters = set()        # raw Counter() families (off-registry)
    registered = {}         # var name -> (ctor kind, instrument name)
    for node in mtree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call):
            fn = node.value.func
            ctor = fn.attr if isinstance(fn, ast.Attribute) else \
                getattr(fn, "id", None)
            var = node.targets[0].id
            if ctor == "Counter":
                counters.add(var)
            elif ctor in _REGISTRY_CTORS:
                args = node.value.args
                iname = args[0].value if args and isinstance(
                    args[0], ast.Constant) else var
                registered[var] = (ctor, iname)
    instrument_vars = counters | set(registered)

    for var in sorted(counters):
        findings.append(
            f"metrics.py: {var} is a raw Counter family off the obs "
            f"registry — invisible to metrics_dump()/Prometheus; "
            f"register it via obs.registry (REGISTRY.counter_family)")

    def refs(func):
        return {n.id for n in ast.walk(func)
                if isinstance(n, ast.Name)} & instrument_vars

    recorders, accessors = {}, {}   # func name -> instrument vars
    for node in mtree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        r = refs(node)
        if not r:
            continue
        if node.name.startswith("record_"):
            recorders[node.name] = r
        elif not node.name.startswith("reset_") \
                and not node.name.startswith("_"):
            accessors[node.name] = r
    recorded_vars = set().union(*recorders.values()) if recorders \
        else set()
    for var in sorted(set(registered) - recorded_vars):
        findings.append(
            f"metrics.py: registered {registered[var][0]} "
            f"'{registered[var][1]}' ({var}) has no record_* recording "
            f"site — dead instrument")

    prof_names = set()
    try:
        for node in ast.walk(ast.parse(profiler_src)):
            if isinstance(node, ast.Name):
                prof_names.add(node.id)
            elif isinstance(node, ast.alias):
                prof_names.add(node.name.split(".")[0])
                if node.asname:
                    prof_names.add(node.asname)
    except SyntaxError as e:
        return [f"profiler.py: syntax error: {e}"]

    # names defined/called across the package (outside metrics.py), plus
    # the ad-hoc recorder sweep: record_* defs in obs/ are part of the
    # telemetry surface; anywhere else they bypass the registry
    usage_names = set()
    allowed_recorders = set(recorders) | {
        n.name for n in mtree.body if isinstance(n, ast.FunctionDef)
        and n.name.startswith("record_")}
    adhoc_defs, called = [], {}     # called: name -> first file
    for fname, src in (usage_srcs or {}).items():
        in_obs = "obs" in fname.replace(os.sep, "/").split("/")
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Name):
                usage_names.add(node.id)
            elif isinstance(node, ast.Attribute):
                usage_names.add(node.attr)
            if isinstance(node, ast.FunctionDef) \
                    and node.name.startswith("record_"):
                if in_obs:
                    allowed_recorders.add(node.name)
                else:
                    adhoc_defs.append((fname, node.lineno, node.name))
            elif isinstance(node, ast.Call):
                f = node.func
                cname = f.id if isinstance(f, ast.Name) else \
                    f.attr if isinstance(f, ast.Attribute) else None
                if cname and cname.startswith("record_"):
                    called.setdefault(cname, fname)
    for fname, lineno, name in adhoc_defs:
        findings.append(
            f"{fname}:{lineno}: ad-hoc recorder '{name}' defined outside "
            f"metrics.py/obs — its counts never reach the obs registry "
            f"(metrics_dump/Prometheus); move the instrument into "
            f"metrics.py")
    if usage_srcs is not None:
        for cname, fname in sorted(called.items()):
            if cname not in allowed_recorders:
                findings.append(
                    f"{fname}: call to unregistered recorder '{cname}' — "
                    f"no such record_* in metrics.py/obs; counts recorded "
                    f"there are invisible to metrics_dump()")

    for rec, vars_ in sorted(recorders.items()):
        acc = [a for a, av in accessors.items() if av & vars_]
        if not acc:
            findings.append(
                f"metrics.py: {rec} records counters {sorted(vars_)} but "
                f"no accessor function exposes them")
        elif not any(a in prof_names for a in acc):
            findings.append(
                f"metrics.py: counter family of {rec} (accessors "
                f"{sorted(acc)}) is not surfaced by any profiler.py API")
        if usage_srcs is not None and rec not in usage_names:
            findings.append(
                f"metrics.py: {rec} is never called anywhere in the "
                f"package — dead counter family")
    return findings


# -------------------------------------------------------------------- style

def check_style(src, fname):
    """Unused imports (F401) and placeholder-less f-strings (F541) — the
    'real errors' ruff subset, self-implemented for ruff-less containers.
    ``__init__.py`` re-export surfaces and ``# noqa`` lines are exempt."""
    findings = []
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [f"{fname}: syntax error: {e}"]
    lines = src.splitlines()

    def noqa(lineno):
        return lineno - 1 < len(lines) and "noqa" in lines[lineno - 1]

    if not fname.endswith("__init__.py"):
        imported = {}   # bound name -> (lineno, display)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for al in node.names:
                    bound = al.asname or al.name.split(".")[0]
                    imported[bound] = (node.lineno, al.name)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue  # compiler directive, not a binding (ruff too)
                for al in node.names:
                    if al.name == "*":
                        continue
                    bound = al.asname or al.name
                    imported[bound] = (node.lineno, al.name)
        used = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
        # names re-exported via __all__ count as used — but ONLY __all__:
        # matching arbitrary string constants would let any message or
        # dict key silently disable the check
        for node in ast.walk(tree):
            if isinstance(node, (ast.Assign, ast.AugAssign)) and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in (node.targets if isinstance(node, ast.Assign)
                              else [node.target])):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Constant) \
                            and isinstance(sub.value, str):
                        used.add(sub.value)
        for bound, (lineno, display) in sorted(imported.items(),
                                               key=lambda kv: kv[1][0]):
            if bound not in used and not noqa(lineno):
                findings.append(
                    f"{fname}:{lineno}: unused import '{display}' (F401)")
    # format specs (":.3f") are themselves JoinedStr nodes — exclude them
    spec_ids = {id(n.format_spec) for n in ast.walk(tree)
                if isinstance(n, ast.FormattedValue)
                and n.format_spec is not None}
    for node in ast.walk(tree):
        if isinstance(node, ast.JoinedStr) and id(node) not in spec_ids \
                and all(isinstance(v, ast.Constant) for v in node.values) \
                and not noqa(node.lineno):
            findings.append(
                f"{fname}:{node.lineno}: f-string without placeholders "
                f"(F541)")
    return findings


# -------------------------------------------------------------------- entry

def _read_tree(root, rel):
    out = {}
    base = os.path.join(root, rel)
    for dirpath, _, files in os.walk(base):
        if "__pycache__" in dirpath:
            continue
        for f in sorted(files):
            if f.endswith(".py"):
                p = os.path.join(dirpath, f)
                with open(p, encoding="utf-8") as fh:
                    out[os.path.relpath(p, root)] = fh.read()
    return out


def run_concurrency(root=REPO, sources=None):
    """The ISSUE 14 concurrency verifier over the WHOLE package (every
    plane: ps/, serving/, parallel/, graph/, obs/, data/ and top-level
    modules) — also part of :func:`run_all`'s tier-1 gate.  ``sources``
    lets a caller that already read the tree skip the second disk walk."""
    eng = concurrency_engine(root)
    return eng.check_concurrency(
        sources if sources is not None else eng.scan_package(root))


def run_all(root=REPO, style_dirs=("hetu_tpu", "tools")):
    """All checks over the repo; returns the flat findings list."""
    pkg = _read_tree(root, "hetu_tpu")
    ps = {k: v for k, v in pkg.items()
          if k.replace(os.sep, "/").startswith("hetu_tpu/ps/")}
    findings = []
    # ISSUE 14: the lock-order pass grew into the repo-wide concurrency
    # verifier — run_concurrency covers the old ps/-local lock-order
    # check (same engine, whole package) plus the new detectors; pkg is
    # the same {relpath: source} map scan_package would rebuild
    findings += run_concurrency(root, sources=pkg)
    findings += check_opcodes(ps)
    findings += check_protocol_alphabet(ps, root=root)
    metrics_key = os.path.join("hetu_tpu", "metrics.py")
    profiler_key = os.path.join("hetu_tpu", "profiler.py")
    findings += check_metrics(pkg[metrics_key], pkg[profiler_key],
                              {k: v for k, v in pkg.items()
                               if k != metrics_key})
    for d in style_dirs:
        for fname, src in sorted(_read_tree(root, d).items()):
            findings += check_style(src, fname)
    return findings


def main(argv=None):
    argv = list(argv if argv is not None else sys.argv[1:])
    conc_only = "--concurrency" in argv
    if conc_only:
        argv.remove("--concurrency")
    if any(a in ("-h", "--help") for a in argv):
        print("usage: hetu_lint.py [--concurrency] [root]")
        return 0
    bad = [a for a in argv if a.startswith("-")]
    if bad:
        print(f"hetu_lint: unknown option {bad[0]!r} "
              f"(usage: hetu_lint.py [--concurrency] [root])")
        return 2
    root = argv[0] if argv else REPO
    findings = run_concurrency(root) if conc_only else run_all(root)
    for f in findings:
        print(f"hetu_lint: {f}")
    if findings:
        print(f"hetu_lint: {len(findings)} finding(s)")
        return 1
    print("hetu_lint: clean" + (" (concurrency)" if conc_only else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
