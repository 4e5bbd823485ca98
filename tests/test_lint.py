"""Tier-1 lint gate: the framework self-lint must be CLEAN, and each of
its detectors must fire on a synthetic violation (a detector that cannot
detect is worse than none — it green-lights drift).

``tools/hetu_lint.py`` statically checks hetu_tpu's own source: PS lock
acquisition-order cycles, OP_* wire-protocol integrity (unique values +
client sender + server dispatch arm per opcode), metrics counters surfaced
by profiler accessors, and the ruff-subset style errors (unused imports,
placeholder-less f-strings).  When a real ruff binary exists it runs too,
against the pyproject.toml config.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import hetu_lint  # noqa: E402


# ------------------------------------------------------------ the tier-1 gate

def test_framework_self_lint_clean():
    """Zero findings over hetu_tpu/ + tools/ — gates every future PR."""
    findings = hetu_lint.run_all(ROOT)
    assert not findings, "\n".join(findings)


def test_ruff_clean_when_available():
    """Run real ruff against pyproject.toml when the environment has it."""
    if shutil.which("ruff") is None:
        pytest.skip("ruff not installed in this container; "
                    "tools/hetu_lint.py covers the F401/F541 subset")
    proc = subprocess.run(
        ["ruff", "check", "hetu_tpu", "tools", "tests"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_opcode_registry_runtime_twin():
    """The import-time opcode registry (satellite of the self-lint check)
    holds every OP_* with a unique value and rejects collisions."""
    from hetu_tpu.ps import dist_store
    from hetu_tpu.ps.opcodes import OPCODES, defop, op_name
    ops = {k: v for k, v in vars(dist_store).items()
           if k.startswith("OP_") and isinstance(v, int)}
    assert len(set(ops.values())) == len(ops)
    for name, val in ops.items():
        assert OPCODES[val] == name
        assert op_name(val) == name
    with pytest.raises(AssertionError, match="collision"):
        defop("OP_TEST_COLLIDER", dist_store.OP_PULL)
    assert op_name(9999).startswith("OP_UNKNOWN")


def test_frame_repr_names_opcode():
    from hetu_tpu.ps.dist_store import OP_PUSH_PULL
    from hetu_tpu.ps.opcodes import frame_repr
    r = frame_repr(OP_PUSH_PULL, table=3, nkeys=128, shard=1)
    assert "OP_PUSH_PULL" in r and "table=3" in r and "shard=1" in r


# ----------------------------------------------- synthetic-violation proofs

def test_lock_order_detects_abba_cycle():
    src = textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def fwd(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def bwd(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
    """)
    findings = hetu_lint.check_lock_order({"synthetic.py": src})
    assert any("cycle" in f and "_a_lock" in f for f in findings), findings


def test_lock_order_detects_cycle_through_method_call():
    """Holding A and CALLING a method that takes B must create the A->B
    edge (the dist_store _apply_push -> _forward pattern)."""
    src = textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def apply(self):
                with self._a_lock:
                    self.mirror()

            def mirror(self):
                with self._b_lock:
                    pass

            def other(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
    """)
    findings = hetu_lint.check_lock_order({"synthetic.py": src})
    assert any("cycle" in f for f in findings), findings


def test_lock_order_detects_nonreentrant_reentry():
    src = textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._x_lock = threading.Lock()

            def outer(self):
                with self._x_lock:
                    self.inner()

            def inner(self):
                with self._x_lock:
                    pass
    """)
    findings = hetu_lint.check_lock_order({"synthetic.py": src})
    assert any("self-deadlock" in f for f in findings), findings


def test_lock_order_allows_rlock_reentry():
    src = textwrap.dedent("""
        import threading

        class S:
            def __init__(self):
                self._x_lock = threading.RLock()

            def outer(self):
                with self._x_lock:
                    self.inner()

            def inner(self):
                with self._x_lock:
                    pass
    """)
    assert hetu_lint.check_lock_order({"synthetic.py": src}) == []


def test_opcodes_detect_value_collision():
    src = "OP_A = 1\nOP_B = 1\n" \
          "def f(x):\n    send(OP_A); send(OP_B)\n" \
          "def g(op):\n    return op == OP_A or op == OP_B\n"
    findings = hetu_lint.check_opcodes({"synthetic.py": src})
    assert any("collision" in f for f in findings), findings


def test_opcodes_detect_missing_dispatch_arm():
    """The mirrored-but-unhandled replication frame: a client sends OP_B
    but no server arm compares against it."""
    src = "OP_A = 1\nOP_B = 2\n" \
          "def f(x):\n    send(OP_A); send(OP_B)\n" \
          "def g(op):\n    return op == OP_A\n"
    findings = hetu_lint.check_opcodes({"synthetic.py": src})
    assert any("OP_B" in f and "dispatch" in f for f in findings), findings
    assert not any("OP_A" in f for f in findings)


def test_opcodes_detect_missing_sender():
    src = "OP_A = 1\nOP_B = 2\n" \
          "def f(x):\n    send(OP_A)\n" \
          "def g(op):\n    return op == OP_A or op == OP_B\n"
    findings = hetu_lint.check_opcodes({"synthetic.py": src})
    assert any("OP_B" in f and "sender" in f for f in findings), findings


def test_opcodes_understand_registry_form():
    src = 'OP_A = defop("OP_A", 1)\nOP_B = defop("OP_WRONG", 2)\n' \
          "def f(x):\n    send(OP_A); send(OP_B)\n" \
          "def g(op):\n    return op == OP_A or op == OP_B\n"
    findings = hetu_lint.check_opcodes({"synthetic.py": src})
    assert any("name mismatch" in f for f in findings), findings


def test_metrics_detect_unsurfaced_counter():
    metrics_src = textwrap.dedent("""
        import collections
        _orphans = collections.Counter()
        _served = collections.Counter()

        def record_orphan(kind):
            _orphans[kind] += 1

        def orphan_counts():
            return dict(_orphans)

        def record_served(kind):
            _served[kind] += 1

        def served_counts():
            return dict(_served)
    """)
    profiler_src = "from .metrics import served_counts\n" \
                   "def fn():\n    return served_counts()\n"
    usage = {"a.py": "record_orphan('x'); record_served('y')"}
    findings = hetu_lint.check_metrics(metrics_src, profiler_src, usage)
    assert any("record_orphan" in f and "not surfaced" in f
               for f in findings), findings
    assert not any("record_served" in f for f in findings)


def test_metrics_detect_recorder_without_accessor():
    metrics_src = textwrap.dedent("""
        import collections
        _c = collections.Counter()

        def record_thing(kind):
            _c[kind] += 1
    """)
    findings = hetu_lint.check_metrics(metrics_src, "", {"a.py":
                                                         "record_thing('x')"})
    assert any("no accessor" in f for f in findings), findings


def test_metrics_detect_unrecorded_registry_instrument():
    """ISSUE 10: a registered counter/histogram/gauge with no record_*
    recording site is dead telemetry — the registry extension must say
    so (one case per instrument kind)."""
    for ctor in ("counter_family", "histogram", "gauge"):
        src = f'_x = REGISTRY.{ctor}("lonely", "doc")\n'
        findings = hetu_lint.check_metrics(src, "", {})
        assert any("no record_* recording site" in f for f in findings), \
            (ctor, findings)
    # a recorded + accessed + surfaced registry instrument is clean
    src = textwrap.dedent("""
        _h = REGISTRY.histogram("fine_us", "doc")

        def record_fine(us):
            _h.observe(us)

        def fine_stats():
            return _h.snapshot()
    """)
    prof = "from .metrics import fine_stats\n"
    findings = hetu_lint.check_metrics(src, prof,
                                       {"a.py": "record_fine(1.0)"})
    assert findings == [], findings


def test_metrics_detect_raw_counter_off_registry():
    """A module-level collections.Counter family bypasses metrics_dump
    — flagged even when recorder/accessor/profiler wiring is right."""
    src = textwrap.dedent("""
        import collections
        _c = collections.Counter()

        def record_c(kind):
            _c[kind] += 1

        def c_counts():
            return dict(_c)
    """)
    prof = "from .metrics import c_counts\n"
    findings = hetu_lint.check_metrics(src, prof, {"a.py": "record_c('x')"})
    assert any("raw Counter family off the obs registry" in f
               for f in findings), findings


def test_metrics_detect_adhoc_recorder_and_unregistered_call():
    """A record_* defined outside metrics.py/obs, or a call to a
    record_* name defined in neither, is an unregistered ad-hoc
    recorder; the same def under hetu_tpu/obs/ is allowed."""
    findings = hetu_lint.check_metrics(
        "", "", {"hetu_tpu/rogue.py":
                 "def record_rogue(k):\n    pass\nrecord_rogue('x')\n"})
    assert any("ad-hoc recorder 'record_rogue'" in f
               for f in findings), findings
    findings = hetu_lint.check_metrics(
        "", "", {"hetu_tpu/other.py": "record_ghost('x')\n"})
    assert any("unregistered recorder 'record_ghost'" in f
               for f in findings), findings
    findings = hetu_lint.check_metrics(
        "", "", {"hetu_tpu/obs/__init__.py":
                 "def record_wrapped(k):\n    pass\n",
                 "hetu_tpu/user.py": "record_wrapped('x')\n"})
    assert not any("record_wrapped" in f for f in findings), findings


def test_style_detects_unused_import_and_bare_fstring():
    src = "import os\nimport sys\nprint(sys.argv)\nx = f'no placeholders'\n"
    findings = hetu_lint.check_style(src, "synthetic.py")
    assert any("unused import 'os'" in f for f in findings), findings
    assert any("F541" in f for f in findings), findings
    # noqa and __init__.py exemptions
    assert hetu_lint.check_style("import os  # noqa\n", "synthetic.py") == []
    assert hetu_lint.check_style("import os\n", "pkg/__init__.py") == []


def test_style_string_constants_do_not_mask_unused_imports():
    """Review regression: only __all__ strings mark an import as used — an
    unrelated message/dict-key string must not disable the check."""
    masked = 'import os\nmsg = "os"\n'
    findings = hetu_lint.check_style(masked, "synthetic.py")
    assert any("unused import 'os'" in f for f in findings), findings
    exported = 'import os\n__all__ = ["os"]\n'
    assert hetu_lint.check_style(exported, "synthetic.py") == []


def test_protocol_alphabet_detects_unmodeled_opcode():
    """ISSUE 20 drift gate: a new OP_* in ps/ that is in neither the
    model's message alphabet nor the allowlist is a finding — a new
    replication opcode cannot silently bypass the model."""
    src = ("OP_A = 1\nOP_NEW = 2\n"
           "def f(x):\n    send(OP_A); send(OP_NEW)\n"
           "def g(op):\n    return op == OP_A or op == OP_NEW\n")
    findings = hetu_lint.check_protocol_alphabet(
        {"synthetic.py": src}, alphabet={"OP_A": "modeled"},
        allowlist={})
    assert any("OP_NEW" in f and "neither" in f for f in findings), \
        findings
    assert not any("OP_A is" in f for f in findings)


def test_protocol_alphabet_detects_double_listing_and_stale_entry():
    src = ("OP_A = 1\n"
           "def f(x):\n    send(OP_A)\n"
           "def g(op):\n    return op == OP_A\n")
    findings = hetu_lint.check_protocol_alphabet(
        {"synthetic.py": src},
        alphabet={"OP_A": "modeled", "OP_GONE": "removed long ago"},
        allowlist={"OP_A": "also exempt?"})
    assert any("OP_A" in f and "BOTH" in f for f in findings), findings
    assert any("OP_GONE" in f and "stale" in f for f in findings), \
        findings


def test_protocol_alphabet_requires_reasons():
    src = ("OP_A = 1\n"
           "def f(x):\n    send(OP_A)\n"
           "def g(op):\n    return op == OP_A\n")
    findings = hetu_lint.check_protocol_alphabet(
        {"synthetic.py": src}, alphabet={}, allowlist={"OP_A": "  "})
    assert any("empty reason" in f for f in findings), findings
    clean = hetu_lint.check_protocol_alphabet(
        {"synthetic.py": src}, alphabet={"OP_A": "modeled"},
        allowlist={})
    assert clean == [], clean


# ------------------------------------------------------------------ layering

def _imported_modules(path):
    """(line, absolute dotted name) of every import in ``path``, at module
    level or inside a function; ``from a import b`` yields ``a`` and
    ``a.b`` (``b`` may be a module)."""
    import ast
    pkg = os.path.relpath(os.path.dirname(path), ROOT).split(os.sep)
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom):
            base = pkg[:len(pkg) - (node.level - 1)] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            yield node.lineno, mod
            for a in node.names:
                yield node.lineno, f"{mod}.{a.name}"


#: what lives above the step: the plan search, the serving plane, and
#: everything outside the package (the root's scripts, tools, benchmark,
#: tests, examples)
_UPWARD = ("hetu_tpu.autoparallel", "hetu_tpu.serving")
_OUTSIDE = {os.path.splitext(n)[0] for n in os.listdir(ROOT)
            if n.endswith(".py") or n in ("tools", "benchmarks", "tests",
                                          "examples")}


@pytest.mark.parametrize("scope,also", [
    ("hetu_tpu/obs", ("hetu_tpu.parallel",)),
    ("hetu_tpu/metrics.py", ("hetu_tpu.parallel",)),
    ("hetu_tpu/ops", ()),
    ("hetu_tpu/graph", ()),
])
def test_lower_layers_import_nothing_above_them(scope, also):
    """Observability, the counters, the ops and the graph executor are
    what the planes are built ON: none of them imports the plan search,
    the serving plane or a script from outside the package — and the
    observability layer nothing of ``parallel`` either (the executor's
    use of ``parallel.zero`` / ``parallel.remat`` and the ops' of
    ``parallel.ring_attention`` is the design)."""
    top = os.path.join(ROOT, scope)
    files = [top] if scope.endswith(".py") else [
        os.path.join(d, n) for d, _, names in os.walk(top)
        for n in names if n.endswith(".py")]
    assert files
    bad = [f"{os.path.relpath(f, ROOT)}:{line}: {mod}"
           for f in files for line, mod in _imported_modules(f)
           if mod.startswith(_UPWARD + also)
           or mod.split(".")[0] in _OUTSIDE]
    assert not bad, "\n".join(bad)
