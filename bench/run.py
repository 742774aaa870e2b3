"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process loads the cell's model, warms up every shape the cell's traffic
uses, measures for ``--seconds`` (whole steps or waves: the window closes at
the end of the first one that ends at or after ``--seconds``), checks what
the timed path produced against the plain reference, and prints one JSON
object as the last line of its output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
the window and reports its per-layer metrics, the device's busy and window
seconds, and the breakdown of device operations and idle gaps.  Every number
the check compared is printed with its limit under ``checks`` and as the
last lines of standard error.

It exits non-zero and prints no result where JAX finds no TPU, or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, harness  # noqa: E402
from bench import trace as trace_mod  # noqa: E402


class Context:
    """What a driver gets: the cell, its model configuration, the seed's
    generators, the store, the window, and a log of earlier lines."""

    def __init__(self, cell, seed: int, seconds: float, trace_dir,
                 store: Path, devices) -> None:
        self.cell = cell
        self.cfg = harness.model_config(cell.config)
        self.seed = seed
        self.seconds = seconds
        self.store = store
        self.devices = devices
        self.eos = int(cell.config["eos_token_id"])
        self.weight_seed = harness.weight_seed(seed)
        self.window = harness.Window(trace_dir)
        self.device_peak_bytes = None

    def rng(self, stream: str):
        return harness.rng(self.seed, stream)

    def read_device_peak(self) -> None:
        """Read the device's peak once the window has closed, before the
        reference runs (a process's peak never falls again)."""
        self.device_peak_bytes = harness.device_peak_bytes(self.devices)

    @staticmethod
    def log(line: str) -> None:
        print(line, flush=True)

    def log_rss(self, where: str) -> None:
        """The process's resident set at a point of set-up."""
        self.log(f"resident set {where}: "
                 f"{harness.proc_status_kib('VmRSS') * 1024} B")


def run_driver(cell, seed: int, seconds: float, trace_dir, devices):
    """Run the cell's driver in its store; returns (context, record)."""
    with harness.StoreDir(cell) as store:
        from repro.core.nvme import filesystem_info
        fs = filesystem_info(str(store))
        print(f"store {fs['path']}: {fs['fstype']} at {fs['mount']}, "
              f"{fs['free_bytes']} bytes free")
        ctx = Context(cell, seed, seconds, trace_dir, store, devices)
        record = harness.load_driver(cell).run(ctx)
    return ctx, record


def run_cell(cell, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """Run one cell and return its result object (see the module
    docstring)."""
    trace_dir = (cell.root / harness.RUN_DIR_NAME / "trace" / cell.name
                 if trace else None)
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    peaks = harness.load_json(cell.root / "bench" / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise harness.BenchError(
            f"device kind {kind!r} is not in bench/peaks.json")
    ctx, record = run_driver(cell, seed, seconds, trace_dir, devices)
    window = ctx.window
    record["peak_flops_per_s"] = peaks["devices"][kind]["bf16_flops_per_s"]
    record["chips"] = cell.chips
    hwm = harness.proc_status_kib("VmHWM")
    print(f"window {window.seconds:.6f} s; compiles inside it "
          f"{window.compiles}; host RSS peak in it {window.host_peak_bytes}"
          f" B ({window.rss_samples} samples); whole-run VmHWM "
          f"{'not reported' if hwm is None else f'{hwm * 1024} B'}; device "
          f"peak_bytes_in_use {ctx.device_peak_bytes} B")
    result = {"correct": False, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": {}}
    if trace:
        t0 = time.perf_counter()
        path = trace_mod.find_trace(trace_dir)
        record["trace"] = trace_mod.reduce_trace(path)
        print(f"trace {path.stat().st_size} B reduced in "
              f"{time.perf_counter() - t0:.3f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = per_layer_metrics(cell, record)
    else:
        e2e = dict(record["e2e"], setup_s=window.setup_s,
                   peak_host_gib=window.host_peak_bytes / 2**30)
        for m in cell.metrics("end_to_end"):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["device"] = dict(harness.device_facts(devices),
                            memory_peak_bytes=ctx.device_peak_bytes)
    if trace:
        result["device"]["busy_s"] = record["trace"]["busy_s"]
        result["device"]["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": record["trace"]["device_ops"],
            "idle_gaps": record["trace"]["idle_gaps"]}
    checks = record["checks"]
    result["correct"] = (compare.passed(checks) and record["failed"] == 0
                         and record["attempted"] > 0)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def per_layer_metrics(cell, record: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read in the run's record."""
    out = {}
    for m in cell.metrics("per_layer"):
        value = harness.load_metric_reader(cell, m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: JAX finds {len(devices)} {devices[0].platform} "
              f"device(s); cell {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    devices = devices[:cell.chips]
    print(f"device {devices[0].platform} {devices[0].device_kind} "
          f"(count {len(devices)} of {len(jax.devices())})")
    with open("/proc/meminfo") as f:
        print(f"host {f.readline().strip()}, {os.cpu_count()} CPUs")
    print(f"compile cache {harness.enable_compile_cache(ROOT)}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
