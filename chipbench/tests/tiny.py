"""Cells of the benchmark cut to a size the CPU runs in seconds, built from
the committed configuration and traffic files by overriding sizes only."""
from __future__ import annotations

import json
import time

from chipbench import bench

#: the v5e's peaks stand in for a device the CPU tests do not have
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 12345


def train_cell(bench_dir=bench.BENCH_DIR) -> bench.Cell:
    """Two layers at a quarter of the width, computed in float32: on the
    CPU the program's bfloat16 rounds otherwise than the chip's."""
    spec = bench.load_json(bench_dir / "configs" / "mamba2-370m.json")
    spec.update(d_model=64, n_layer=2, vocab_size=500, d_state=16, headdim=16)
    spec["program"]["model"].update(num_layers=2, d_model=64, vocab_size=500,
                                    ssm_state=16, ssm_head_dim=16,
                                    ssm_chunk=32, dtype="float32")
    spec["program"]["head"].update(midx_k=8, num_negatives=16,
                                   refresh_every=3)
    tr = bench.load_json(bench_dir / "traffic" / "train-pooled.json")
    tr.update(batch=2, seq=128, distinct_batches=4)
    lim = bench.load_json(bench_dir / "cells" /
                          "mamba2-370m.train-pooled.json")
    return bench.Cell("mamba2-370m.train-pooled", 1, spec, tr, lim, bench_dir)


def serve_cell(bench_dir=bench.BENCH_DIR) -> bench.Cell:
    """Two layers of four experts at the published widths (so the logits
    spread as at full size), computed in float32: on the CPU the program's
    bfloat16 rounds otherwise than the chip's."""
    spec = bench.load_json(bench_dir / "configs" /
                           "granite-moe-1b-a400m.json")
    spec.update(num_hidden_layers=2, num_local_experts=4,
                num_experts_per_tok=2, vocab_size=4000)
    spec["program"]["model"].update(num_layers=2, num_experts=4,
                                    num_experts_per_tok=2, vocab_size=4000,
                                    capacity_factor=2.0, dtype="float32")
    spec["program"]["head"].update(midx_k=8)
    spec["program"]["serve"].update(max_slots=4, max_seq=48)
    tr = bench.load_json(bench_dir / "traffic" / "serve-closed32.json")
    tr.update(clients=4, prompt_buckets=[8, 16], prompt_weights=[0.5, 0.5],
              output_buckets=[16, 32], output_weights=[0.5, 0.5],
              warm_seconds=0.5, check_requests=6, drain_seconds=30)
    lim = bench.load_json(bench_dir / "cells" /
                          "granite-moe-1b-a400m.serve-closed32.json")
    return bench.Cell("granite-moe-1b-a400m.serve-closed32", 1, spec, tr, lim,
                      bench_dir)


def ctx(cell: bench.Cell, seconds: float = 1.5, seed: int = SEED,
        trace: bool = False) -> bench.Ctx:
    return bench.Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     t_process=time.perf_counter())


def run(cell: bench.Cell, seconds: float = 1.5, fault=None
        ) -> tuple[dict, dict]:
    """Drive a cell on the CPU; returns (the result line as a dict, rec)."""
    bench_json = bench.load_json(cell.bench_dir.parent / "BENCHMARK.json")
    line, rec = bench.run_cell(bench_json, cell, ctx(cell, seconds),
                               {"platform": "cpu", "kind": "cpu", "count": 1},
                               peaks=PEAKS, fault=fault)
    return json.loads(line), rec
