"""Readings that set the output check's limits: the control and the
faults, on the chip at the cell's own size.  The benchmark's runs never
run this.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        [--seconds 10]

For each seed it prints one JSON line of readings:

* a ``finetune`` cell: the reference computed with float8 matrix products
  put in the program's place (the control) and the reference with half of
  each batch left out (a fault), each compared with the float32 reference
  over the cell's steps, on the batches the cell feeds for that seed;
* a ``batch_serve`` cell: one run of the cell (``--seconds`` long), its
  own served-token gap, and the control's: at each position of the same
  prompts and served tokens, the float32 reference's gap of the token the
  float8 reference puts first.

The run of a state left unchanged needs no reading: its change is 0 on
every leaf, a gap of 1.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, harness, reference, traffic_gen  # noqa: E402

FINETUNE_STEPS = 3      # two warm-up steps and one window step


def _value(checks: dict) -> dict:
    return {k: c["value"] for k, c in checks.items()}


def finetune(cell, seed: int) -> dict:
    sizes = reference.Sizes(cell.config)
    feed = traffic_gen.packed_batches(
        cell.traffic, harness.rng(seed, "traffic"),
        vocab=sizes.vocab, eos=int(cell.config["eos_token_id"]))
    batches = [next(feed) for _ in range(FINETUNE_STEPS)]
    adam = {"lr": cell.workload["lr"], "beta1": 0.9, "beta2": 0.999,
            "eps": 1e-8}
    ws = harness.weight_seed(seed)
    ref = reference.train(sizes, ws, batches, adam)
    out = {}
    for name, kw in (("control_fp8", {"matmul": "fp8"}),
                     ("fault_half_batch", {"half_batch": True})):
        other = reference.train(sizes, ws, batches, adam, **kw)
        out[name] = _value(compare.train_checks(other, ref,
                                                cell.workload["limits"]))
    return out


def batch_serve(cell, seed: int, seconds: float, devices) -> dict:
    from bench import run
    from bench.drivers.batch_serve import check_served
    _ctx, record = run.run_driver(cell, seed, seconds, None, devices)
    decode = cell.workload["decode"]
    gaps = check_served(reference.Sizes(cell.config),
                        harness.weight_seed(seed), record["sample"],
                        decode["batch"], decode["max_seq"], matmul="fp8")
    return {"program": _value(record["checks"]),
            "control_fp8": {"served_logit_gap": max(gaps)},
            "served_tokens": len(gaps)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.driver == "finetune":
            readings = finetune(cell, seed)
        else:
            readings = batch_serve(cell, seed, args.seconds, devices[:1])
        print(json.dumps({"workload": cell.name, "seed": seed, **readings}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
