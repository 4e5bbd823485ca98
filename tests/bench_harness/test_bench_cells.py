"""Whole runs of the harness at CPU size: cells added as files only, the
references against the system, the controls and the broken timed paths."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, REAL_OF, ROOT, TINY_SERVE, TINY_TRAIN

from benchmarks import harness, weights


def _run(root, workload, trace=False, seed=3000000019, seconds=1.0,
         control=False):
    return harness.run_cell(workload, seed, seconds, trace,
                            files=harness.Files(root), require_tpu=False,
                            out_dir=os.path.join(root, "out"),
                            control=control)


def _driver(root, workload, seed=7, **cfg_over):
    """The cell's driver, set up, outside ``run_cell`` (for the controls)."""
    import importlib
    files = harness.Files(root)
    cell = files.cell(workload)
    cfg = dict(files.config(cell["config"]), **cfg_over)
    mix = files.mix(cell["traffic"])
    mods = {k: importlib.import_module(f"benchmarks.{d}.{n}") for k, d, n in (
        ("driver", "drivers", mix["driver"]),
        ("system", "systems", cfg["system"]),
        ("reference", "reference", cfg["reference"]))}
    d = mods["driver"].Driver(cfg=cfg, mix=mix, seed=seed,
                              system=mods["system"],
                              reference=mods["reference"],
                              compiles=harness.CompileLog.get(),
                              log=harness.log)
    d.setup()
    return d, files.limits(workload)


@pytest.mark.parametrize("workload", [TINY_TRAIN, TINY_SERVE])
def test_a_cell_added_as_files_only_runs_and_is_correct(tiny_root, workload):
    """A configuration, a mix and limits that exist only as files under a
    root are found by the names in ``BENCHMARK.json``; the result line has
    the contract's keys, the cell's end-to-end metrics and nothing at 0."""
    out = _run(tiny_root, workload)
    assert list(out)[-1] == "compared" and out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    bench = harness.Files(tiny_root).bench
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


def test_a_metric_added_as_a_file_only_is_read(tiny_root):
    """One reader file and one ``per_layer`` entry: no code is edited."""
    data = os.path.join(tiny_root, "benchmarks")
    with open(os.path.join(data, "metrics", "steps_done.train.py"), "w") as f:
        f.write("MOVES = 'train_tokens_per_s'\n\n\ndef read(run):\n"
                "    return float(run['window']['steps'])\n")
    files = harness.Files(tiny_root)
    files.bench["per_layer"].append(
        {"name": "steps_done.train", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "executor",
         "moves": "train_tokens_per_s", "workloads": [TINY_TRAIN]})
    run = {"window": {"steps": 12, "seconds": 3.0}, "trace": None,
           "peaks": None}
    got = {m["name"]: files.reader(m["name"])(run)
           for m in files.metrics("per_layer", TINY_TRAIN)}
    assert got["steps_done.train"] == 12.0
    assert got["step_ms.train"] == 250.0
    # a reader that finds nothing to read returns nothing, never 0
    assert got["flash_roofline"] is None and got["mfu_pct.train"] is None
    assert got["device_idle_pct.train"] is None


def test_train_reference_matches_the_system_in_float32(tiny_root):
    """Same seed, same batches, float32 on one backend: the plain
    reference and ``ht.Executor`` agree to rounding on every compared
    number — so what the chip shows between them is precision, not model."""
    d, _ = _driver(tiny_root, TINY_TRAIN)
    assert d.cfg["compute_dtype"] is None
    numbers = d.check()
    assert max(numbers.values()) < 2e-4, numbers
    assert all(numbers[f"loss_gap_step{i}"] < 1e-6 for i in (1, 2, 3))


@pytest.mark.parametrize("workload", [TINY_TRAIN, TINY_SERVE])
def test_the_control_in_the_programs_place_is_not_correct(tiny_root,
                                                          workload):
    """A whole run whose comparison is handed the reference computed in
    the configuration's ``control_precision`` (fp8 products; an
    all-bfloat16 forward) in the program's place: judged by the REAL
    cell's limits, ``correct`` comes out false."""
    out = _run(tiny_root, workload, control=True)
    assert out["correct"] is False, out["compared"]
    over = [k for k, c in out["compared"].items() if c["value"] > c["limit"]]
    assert over and set(over) <= {"grad_angle_gap", "grad_norm_gap",
                                  "delta_norm_gap", "logit_gap_sq_mean"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_train_run_with_the_timed_path_broken_is_not_correct(
        tiny_root, monkeypatch, fault):
    from benchmarks.systems import bert_mlm
    if fault == "state_unchanged":
        real_init = bert_mlm.System.__init__

        def init(self, cfg, mix, w):
            real_init(self, cfg, mix, w)
            self._given = {k: v + 0 for k, v in w.items()}
        monkeypatch.setattr(bert_mlm.System, "__init__", init)
        # the step hands back the parameters it was given
        monkeypatch.setattr(bert_mlm.System, "params",
                            lambda self: self._given)
    else:
        real = bert_mlm.System.feed

        def feed(self, batch):  # the second half of the rows is left out
            batch = dict(batch)
            labels = batch["masked_lm_labels"].copy()
            labels[len(labels) // 2:] = -1
            batch["masked_lm_labels"] = labels
            return real(self, batch)
        monkeypatch.setattr(bert_mlm.System, "feed", feed)
    out = _run(tiny_root, TINY_TRAIN)
    assert out["correct"] is False, out["compared"]


def test_serve_reference_puts_every_served_token_first(tiny_root):
    """Float32 on one backend: chunked prefill into the cache and one-token
    decode out of it, in a mixed batch, serve exactly the tokens the plain
    full-sequence forward puts first."""
    out = _run(tiny_root, TINY_SERVE)
    assert out["compared"]["logit_gap_max"]["value"] < 1e-4
    assert out["compared"]["logit_gap_sq_mean"]["value"] < 1e-9


def test_serve_run_with_a_token_altered_is_not_correct(tiny_root,
                                                       monkeypatch):
    """Every 7th request hands back one token other than the engine's."""
    from benchmarks.systems import gpt2_decode
    real = gpt2_decode.System.submit
    count = [0]

    class Altered:
        def __init__(self, stream):
            self.token = stream.token

            def result(timeout=None):
                tokens = list(stream.result(timeout))
                tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 512
                return tokens
            self.result = result

    def submit(self, prompt, max_new):
        count[0] += 1
        stream = real(self, prompt, max_new)
        return Altered(stream) if count[0] % 7 == 0 else stream
    monkeypatch.setattr(gpt2_decode.System, "submit", submit)
    out = _run(tiny_root, TINY_SERVE)
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["logit_gap_max"]["value"] \
        > out["compared"]["logit_gap_max"]["limit"]


def test_serve_run_whose_cache_grows_inside_the_window_fails(tiny_root):
    """Set-up that does not bring the engine to its long-run state: the
    window opens at the first completion, longer requests follow, and the
    run is refused rather than measured."""
    path = os.path.join(tiny_root, "benchmarks", "traffic", "tiny-chat.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(prime={"prompt": 2, "output": 2}, warmup_requests=1)
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(RuntimeError, match="did not hold its state"):
        _run(tiny_root, TINY_SERVE)


@pytest.mark.parametrize("workload", [TINY_TRAIN, TINY_SERVE])
def test_no_full_collection_from_set_up_to_free(tiny_root, monkeypatch,
                                                workload):
    """From the end of priming (before the first request of the warm-up
    traffic) to ``free`` the old generation's threshold is out of reach: a
    full collection walks what set-up built and the run's own records, with
    every stream waiting, and frees none of it.  The young generations keep
    their thresholds, and ``free`` restores the old one's before it
    collects, so the program's cycles let go of their device memory."""
    import gc
    from benchmarks.drivers import closed_loop_decode
    was = gc.get_threshold()
    seen = []
    real = closed_loop_decode.Driver._settle_heap

    def settle(self):
        seen.append((self.submitting, self.t0))
        real(self)
    monkeypatch.setattr(closed_loop_decode.Driver, "_settle_heap", settle)
    d, _ = _driver(tiny_root, workload)
    assert gc.get_threshold() == (*was[:2], 1 << 30)
    if workload == TINY_SERVE:
        assert seen == [(False, None)] and d.t0 is not None
        d.window(0.2, None)
    d.free()
    assert gc.get_threshold() == was


@pytest.mark.parametrize("stated", [None, 3])
def test_the_training_system_keeps_seconds_of_steps_in_flight(tiny_root,
                                                              stated):
    """The executor is built to run ``steps_in_flight`` steps ahead of the
    oldest it waits for (48 where the mix states none: five seconds of the
    bert cell's steps, so a host that stands still leaves the chip fed),
    and the process's environment is as it was."""
    from benchmarks.systems import bert_mlm
    if stated is not None:
        path = os.path.join(tiny_root, "benchmarks", "traffic",
                            "tiny-mlm.json")
        with open(path) as f:
            mix = json.load(f)
        mix["steps_in_flight"] = stated
        with open(path, "w") as f:
            json.dump(mix, f)
    assert "HETU_ASYNC_WINDOW" not in os.environ
    d, _ = _driver(tiny_root, TINY_TRAIN)
    assert d.sys.ex._async_window == (stated or bert_mlm.STEPS_IN_FLIGHT)
    assert bert_mlm.STEPS_IN_FLIGHT * 0.108 > 4
    assert "HETU_ASYNC_WINDOW" not in os.environ
    # the window dispatches past its close and counts what it waited for
    run = d.window(0.3, None)
    assert run["window"]["steps"] % int(d.mix["block_steps"]) == 0
    assert run["window"]["seconds"] >= 0.3
    d.free()


def test_run_py_refuses_a_backend_that_is_not_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         REAL_OF[TINY_TRAIN], "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "refused" in p.stderr


def test_weights_are_the_seeds_and_take_a_large_seed():
    spec = {"a": ((4, 8), 0.0, 0.02), "b": ((8,), 1.0, 0.02)}
    one = weights.make(spec, 3000000019)
    two = weights.make(spec, 3000000019)
    other = weights.make(spec, 3000000019 - 2 ** 31)
    assert all(np.array_equal(one[k], two[k]) for k in spec)
    assert not np.array_equal(one["a"], other["a"])
    assert one["a"].shape == (4, 8) and abs(float(one["b"].mean()) - 1) < 0.05
