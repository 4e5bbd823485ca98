"""What ``chip_smoke.py`` and the accelerator entry points promise off the
chip: the phase functions run at tiny widths on the CPU, nothing measures
on a backend that is not the TPU, importing the package initialises no
backend, and the compile cache lives where the one rule says."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def test_train_phase_tiny_on_cpu():
    """The same function the chip runs, at bert-tiny: finite falling loss
    near ln(vocab), zero compiles and zero plan misses after warm-up."""
    import chip_smoke
    out = chip_smoke.train(batch=4, seq_len=64, size="tiny", warmup=2,
                           steps=3)
    assert out["losses"][-1] < out["losses"][0]
    assert out["run_plan"]["plan_cache_hit"] >= 3
    assert out["flash_in_hlo"] is False        # CPU: the XLA path, counted
    assert set(out["flash_fallbacks"]) == {"backend:cpu"}
    # 15 % of 64 positions in whole eights: the head ran on 16 rows each
    assert out["mlm_head"]["rows"] == "16of64:gathered"
    assert out["mlm_head"]["rows_over_capacity"] == 0


def test_decode_phase_tiny_on_cpu():
    """The same function the chip runs, at gpt2-tiny: solo, batched and
    full-sequence forward agree (exactly, in f32 on one backend)."""
    import chip_smoke
    out = chip_smoke.decode(prompt_lens=(3, 8, 17), max_new=6, max_slots=4,
                            max_len=32, size="tiny", tol=1e-4)
    assert out["solo_vs_full_forward"]["near_tie_flips"] == []
    assert out["batched_vs_solo"]["near_tie_flips"] == []
    assert out["runs"]["batched_again"]["compile_requests"] == 0
    assert out["decode_prefill_steps"] > 0       # chunked prefill ran


def _run(args, **env):
    return subprocess.run(
        [sys.executable] + args, cwd=ROOT, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_chip_smoke_refuses_a_cpu_backend():
    """No accelerator: non-zero exit, no phase run, no result printed."""
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_imports_initialise_no_backend():
    """A parent that has touched jax holds the chip: importing the package,
    the launcher, the serving plane, the models and the entry script
    must initialise no backend (one process per chip)."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import hetu_tpu, hetu_tpu.launcher, hetu_tpu.serving\n"
        "import hetu_tpu.models, chip_smoke\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('clean')\n" % ROOT)
    proc = _run(["-c", code])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "clean"


def test_compile_cache_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code (jax
    already has it).  Unset: the fixed ``<checkout>/.jax_cache``.  Only
    the two thresholds are set either way; a CPU process keeps jax's
    default."""
    import jax
    from hetu_tpu.graph import executor as ex

    set_dirs = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            set_dirs.append(value)       # recorded, not applied
        elif name.startswith("jax_persistent_cache_min"):
            assert value in (0, ex.COMPILE_CACHE_MIN_COMPILE_SECS)
        else:
            real_update(name, value)
    monkeypatch.setattr(jax.config, "update", spy)

    def configure(backend, env_dir):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(ex, "_compile_cache_configured", False)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        del set_dirs[:]
        ex.configure_compile_cache()
        return list(set_dirs)

    assert configure("tpu", "/somewhere/else") == []
    assert configure("tpu", None) == [os.path.join(ROOT, ".jax_cache")]
    assert configure("cpu", None) == []
    assert "HETU_COMPILE_CACHE_DIR" not in open(ex.__file__).read()


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    """A utilisation against a guessed peak is not a measurement."""
    import jax
    from hetu_tpu.autoparallel import device_peak_flops

    class _Dev:
        device_kind = "TPU v99 hyper"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(ValueError, match="TPU v99 hyper"):
        device_peak_flops()
    _Dev.device_kind = "TPU v5 lite"
    assert device_peak_flops() == (197e12, "TPU v5 lite")
    # and off the TPU there is no peak at all, not a placeholder
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert device_peak_flops() == (None, "TPU v5 lite")


def test_native_store_is_keyed_by_source_hash(tmp_path, monkeypatch):
    """A copied, stale ``.so`` is never loaded: the library's name carries
    a hash of the source it was built from."""
    from hetu_tpu.ps import build
    so = build._so_path()
    assert os.path.basename(so).startswith("libhetu_ps.")
    src = tmp_path / "ps_store.cc"
    src.write_bytes(open(build._SRC, "rb").read() + b"\n// edited\n")
    monkeypatch.setattr(build, "_SRC", str(src))
    assert build._so_path() != so
