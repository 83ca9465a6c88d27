"""Readings that set a cell's limits (cells/<cell>.json): the program's
sound runs over many seeds (the lower reading is their largest), and on
some of them the float8 control and, for training, the half-batch fault,
each put in the program's place (the upper reading is their smallest).
One process, short windows, at the cell's own size; run on the chip:

    python3 chipbench/tools/control.py --workload <cell> --seeds 1 2 3 ... \\
        [--controls 3] [--seconds 3]

Prints one JSON line per seed and reading.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="on how many of the seeds to read the control")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()

    from chipbench import bench
    b = bench.load_json(bench.REPO / "BENCHMARK.json")
    cell = bench.load_cell(b, args.workload)
    bench.device_check(cell.chips)
    bench.use_checkout_cache()
    training = cell.traffic["driver"] == "train_lm"
    for n, seed in enumerate(args.seeds):
        control = n < args.controls
        ctx = bench.Ctx(cell=cell, seed=seed, seconds=args.seconds,
                        trace=False, t_process=time.perf_counter())
        kw = {} if training else {"control": control}
        rec = cell.driver.run(ctx, **kw)
        out = {"seed": seed, "program": rec["readings"],
               "setup_s": rec["setup_s"], "reference_s": rec["reference_s"]}
        if not training and control:
            out["control"] = rec["control_readings"]
        if training and control:
            out.update(train_controls(cell, seed, rec["ref_readings"]))
        del rec
        gc.collect()
        print(json.dumps(out), flush=True)


def train_controls(cell, seed: int, f32: dict) -> dict:
    """The float8 reference, and the reference on half of each batch (the
    mean over the rest), each against the float32 reference."""
    import jax
    from chipbench.drivers import train_lm
    spec, tr, ref = cell.spec, cell.traffic, cell.reference
    n = tr["check_steps"]
    cfg, keys, params, idx, (toks, labs) = train_lm.inputs(
        spec, tr, ref, seed, n)
    step_keys = [jax.random.fold_in(keys[3], i) for i in range(n)]
    m = cfg.head.num_negatives
    full = [(toks[i], labs[i]) for i in range(n)]
    half = [(t[: t.shape[0] // 2], y[: y.shape[0] // 2]) for t, y in full]
    f8 = ref.train_readings(spec, params, idx, full, step_keys,
                            tr["optimizer"], m, low=True)
    hb = ref.train_readings(spec, params, idx, half, step_keys,
                            tr["optimizer"], m)
    return {"control": train_lm.readings(f8, f32)[0],
            "half_batch": train_lm.readings(hb, f32)[0]}


if __name__ == "__main__":
    main()
