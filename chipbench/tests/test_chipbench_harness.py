"""The harness on the CPU: it refuses to run without a TPU, and its window,
result line and comparison work at a size the CPU holds."""

import json
import os
import subprocess
import sys

import jax
import numpy as np

import cells
import check
import run
import tiny


def test_run_exits_nonzero_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite_moe.ef.w1", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cells.CHECKOUT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 2
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def _run(cell, monkeypatch, seed=2**33 + 11):
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    return run.run_cell(cell, seed, 0.5, False, jax.devices())


def test_window_and_last_line(monkeypatch, capsys):
    out = _run(tiny.cell(), monkeypatch)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {e["name"] for e in cells.benchmark()["end_to_end"]}
    assert all(m["value"] > 0 for k, m in out["metrics"].items() if k != "peak_hbm_gib")
    assert out["device"]["count"] == 1
    for name, entry in out["compared"].items():
        assert entry["value"] <= entry["limit"], name
    info = json.loads(capsys.readouterr().out.strip().splitlines()[0])["info"]
    assert info["steps"] == out["attempted"]
    json.dumps(out)


def test_no_limit_is_not_correct():
    assert not check.judge({"loss": 0.0}, {})
    assert check.judge({"loss": 0.0}, {"loss": 0.0})
    assert check.judge({"loss": 1.0, "grad": 0.0}, {"grad": 0.1}, {"loss": "no upper reading"})
    assert not check.judge({"loss": 1.0, "grad": 0.2}, {"grad": 0.1}, {"loss": "no upper reading"})


def test_control_fails_where_the_program_passes():
    """The float32 reference computed in float8 where the program computes in
    bfloat16, put in the program's place: at least one number is over its
    limit."""
    for moe in (True, False):
        cell = tiny.cell(moe=moe)
        for seed in (0, 1):
            pool = run.tokens.batches(seed, 3, cell.global_rows, cell.seq, cell.config["vocab_size"])
            devs = jax.devices()
            ref = run.reference_readings(cell, seed, pool, devs)
            control = check.compare(run.reference_readings(cell, seed, pool, devs, precision="fp8"), ref)
            assert not check.judge(control, cell.limits), control


def test_sign_counts_by_hand():
    """Elements under the floor times the leaf's rms are not counted; of the
    rest, those of another sign (or zero) in ``got`` are."""
    want = {"a": np.array([4.0, -4.0, 0.1, -0.1]), "b": np.array([[1.0, -1.0], [1.0, 1.0]])}
    got = {"a": np.array([3.0, 4.0, -0.1, 0.1]), "b": np.array([[1.0, 0.0], [-2.0, 1.0]])}
    # rms: a sqrt(8.005), b 1
    counted, bad = check.sign_counts(got, want, floors=(0.01, 0.5, 2.0))
    assert counted.tolist() == [[4, 2, 0], [4, 4, 0]]
    assert bad.tolist() == [[3, 1, 0], [2, 2, 0]]
    # at the floor in use (1 x rms), 1 of a's 2 counted elements and 2 of b's 4 differ
    assert check.SIGN_FLOOR == 1.0
    assert check.sign_share(got, want, np.array([True, True])) == 3 / 6
    assert check.sign_share(got, want, np.array([True, False])) == 1 / 2
