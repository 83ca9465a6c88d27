"""The benchmark's harness on the CPU: it refuses to run without a chip, it
finds a cell's files by name, and its result line has the contract's keys."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from chipbench import bench, peaks, stats
from chipbench.counts import granite_moe, mamba2, midx_probs, sampled_ce
from chipbench.tests import tiny

REPO = bench.REPO


def _run_cli(cwd: pathlib.Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "mamba2-370m.train-pooled", "--seed", str(2**31 + 5), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    p = _run_cli(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_result_line_has_the_contract_keys():
    line = json.loads(bench.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.0, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        compared={"loss_rel": {"value": 1e-5, "limit": 1e-3}}))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]


def test_compare_fails_missing_nan_and_over_limit():
    ok, out = bench.compare({"a": 0.5, "b": float("nan")}, {"a": 1.0})
    assert ok and out == {"a": {"value": 0.5, "limit": 1.0}}
    assert not bench.compare({"a": 2.0}, {"a": 1.0})[0]
    assert not bench.compare({"b": float("nan")}, {"b": 1.0})[0]
    assert not bench.compare({}, {"a": 1.0})[0]


def test_every_metric_has_a_reader_and_every_cell_its_files():
    b = bench.load_json(REPO / "BENCHMARK.json")
    for m in b["end_to_end"] + b["per_layer"]:
        assert (bench.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for w in b["workloads"]:
        cell = bench.load_cell(b, w["name"])
        assert cell.reference and cell.driver
        assert set(cell.limits["limits"])
        kinds = {k for k in ("end_to_end", "per_layer")
                 if bench.metrics_of(b, w["name"], k)}
        assert kinds == {"end_to_end", "per_layer"}
        assert "setup_s" in [m["name"] for m in
                             bench.metrics_of(b, w["name"], "end_to_end")]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.for_kind("some other chip")
    assert peaks.for_kind("TPU v5 lite")["bf16_flops"] == 197e12


def test_counts_against_hand_counts():
    # sampled CE, T=2, M=3, D=4: forward 2·2·3·4 + 2·2·4 = 64, backward
    # 4·2·3·4 + 3·2·4 = 120; bytes: rows (2·2+3)·4·4 = 112, ids and log q
    # (2·3+2)·4 = 32, loss and lse 16; backward again plus dh, dpe, dne 112
    # and dlq 12
    f, b = sampled_ce.per_sequence(2, 3, 4)
    assert f == 64 + 120
    assert b == (112 + 32 + 16) + (112 + 32 + 16 + 112 + 12)
    # midx tables, 2 rows, K=3, D=4: 2·(4·3·4 + 2·9) = 132 FLOPs; rows
    # 2·(4 + 9 + 1)·4 = 112 bytes, codebooks and counts (24 + 9)·4 = 132
    assert midx_probs.per_call(2, 3, 4) == (132.0, 244.0)
    # mamba2-370m: projections 13,172,736 and SSD 1,605,632 per layer and
    # token, head 2·1025·1024, forward ×3 for training
    spec = bench.load_json(bench.BENCH_DIR / "configs" / "mamba2-370m.json")
    fwd = 48 * (13_172_736 + 1_605_632) + 2_099_200
    assert mamba2.forward_per_token(spec, 1024) == fwd
    assert mamba2.train_per_token(spec, 1024) == 3 * fwd
    # granite: attention 1024·64·(32+16) = 3,145,728, router 32,768,
    # experts 8·3·1024·512 = 12,582,912, per layer; 24 layers
    g = bench.load_json(bench.BENCH_DIR / "configs" /
                        "granite-moe-1b-a400m.json")
    assert granite_moe.active_weights(g) == 24 * (3_145_728 + 32_768 +
                                                 12_582_912)
    assert granite_moe.output_token(g, 9) == (
        2 * granite_moe.active_weights(g) + 24 * 4 * 16 * 64 * 10
        + 2 * 49155 * 1024)


def test_stats_percentile_and_window():
    assert stats.percentile([], 95) is None
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    rec = {"window": (10.0, 12.0)}
    assert stats.window_s(rec) == 2.0
    assert stats.in_window(rec, 11.0) and not stats.in_window(rec, 12.5)


def test_files_added_in_a_new_directory_are_found_and_run(tmp_path):
    """A configuration, a traffic mix, a cell and a metric, added as files
    beside a copy of the harness, run without an edit to the harness."""
    root = tmp_path / "checkout"
    bdir = root / "chipbench"
    shutil.copytree(bench.BENCH_DIR, bdir,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    base = tiny.train_cell()
    spec = dict(base.spec, name="tiny-lm")
    (bdir / "configs" / "tiny-lm.json").write_text(json.dumps(spec))
    shutil.copy(bdir / "configs" / "mamba2-370m.ref.py",
                bdir / "configs" / "tiny-lm.ref.py")
    (bdir / "traffic" / "train-short.json").write_text(
        json.dumps(base.traffic))
    (bdir / "cells" / "tiny-lm.train-short.json").write_text(
        json.dumps(base.limits))
    (bdir / "metrics" / "train_steps_per_s.py").write_text(
        "def read(rec):\n"
        "    return len(rec['steps']) / (rec['window'][1] - rec['window'][0])\n")
    b = bench.load_json(REPO / "BENCHMARK.json")
    b["workloads"].append({"name": "tiny-lm.train-short", "config": "tiny-lm",
                           "traffic": "train-short", "chips": 1,
                           "why": "test"})
    b["end_to_end"].append({"name": "train_steps_per_s", "unit": "steps/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["tiny-lm.train-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = bench.load_cell(b, "tiny-lm.train-short", bench_dir=bdir)
    assert cell.spec["name"] == "tiny-lm"
    line, _ = bench.run_cell(b, cell, tiny.ctx(cell, seconds=0.5),
                             {"platform": "cpu", "kind": "cpu", "count": 1},
                             peaks=tiny.PEAKS)
    out = json.loads(line)
    assert out["correct"], out["compared"]
    assert out["metrics"]["train_steps_per_s"]["value"] > 0
    # train_tokens_per_s lists the cells it is read in; this is not one
    assert set(out["metrics"]) == {"setup_s", "train_steps_per_s"}
