"""Provenance stamping shared by the ``tools/`` artifact writers.

Side-effect-free on import (no jax, no env-gated config mutation) — tools
that must control backend initialisation order (tools/calibrate_tpu.py)
can import this before touching jax.

Every artifact a tool writes carries ``git_sha`` (HEAD when it was made),
``workload`` (the knobs that define it — canonical; no loose duplicates
elsewhere in the artifact) and ``workload_hash`` (sha256[:12] of the
canonical workload JSON).  Artifacts whose own schema already exposes the
knobs top-level for programmatic consumers (flash_ab's geometry) embed
only the hash.  ``artifacts/README.md`` lists the committed artifacts,
each with its writer and its reader.
"""
import hashlib
import json
import os
import subprocess

_ROOT = os.path.dirname(os.path.abspath(__file__))


def git_sha():
    """HEAD sha at measurement time (12 hex), or None outside a checkout."""
    try:
        proc = subprocess.run(
            ["git", "-C", _ROOT, "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10)
        return proc.stdout.strip()[:12] or None if proc.returncode == 0 \
            else None
    except (OSError, subprocess.TimeoutExpired):
        return None


def workload_hash(workload):
    blob = json.dumps(workload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def provenance(workload, embed_workload=True):
    """Uniform provenance block: the sha ties the number to the code that
    produced it, the hash to the exact workload.  ``embed_workload=False``
    for artifacts whose own schema already carries the knobs top-level."""
    out = {"git_sha": git_sha(), "workload_hash": workload_hash(workload)}
    if embed_workload:
        out["workload"] = dict(workload)
    return out
