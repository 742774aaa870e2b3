"""Shared machinery of the benchmark: finding a cell's files by name,
building its model configuration, seeds, the measured window, host and
device memory readings, and the persistent compilation cache.

Everything a cell needs is found from ``BENCHMARK.json`` by name:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: the traffic mix, read by
  :mod:`bench.traffic_gen`;
* ``bench/workloads/<cell>.json``: the driver (``bench/drivers/<driver>.py``)
  and its settings, and the limits of the output check;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a cell, a configuration or a per-layer metric therefore adds files
and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import threading
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# Compile cache, store and traces of a run; listed in .gitignore.
RUN_DIR_NAME = ".bench_run"

# Published config.json keys checked against the repo's ModelConfig field
# that runs them.
HF_TO_REPO = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
}
HIDDEN_ACT_TO_REPO = {"silu": "swiglu"}


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (missing file, no chip, ...)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files."""

    name: str
    chips: int
    config: dict      # bench/configs/<config>.json
    traffic: dict     # bench/traffic/<traffic>.json
    workload: dict    # bench/workloads/<cell>.json
    spec: dict        # the whole BENCHMARK.json
    root: Path        # the checkout: BENCHMARK.json and bench/

    @property
    def driver(self) -> str:
        return self.workload["driver"]

    def metrics(self, section: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those without a ``workloads`` key and those that list it."""
        return [m for m in self.spec[section]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        known = sorted(w["name"] for w in spec["workloads"])
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{known}")
    entry = entries[0]
    bench = root / "bench"
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=load_json(bench / "configs" / f"{entry['config']}.json"),
        traffic=load_json(bench / "traffic" / f"{entry['traffic']}.json"),
        workload=load_json(bench / "workloads" / f"{name}.json"),
        spec=spec, root=root)


def model_config(config: dict):
    """The repo's ModelConfig for a configuration file: the registry entry
    with the file's overrides, checked key by key against the published
    numbers the file states as run.  A registry that drifts from the file
    stops the run rather than measuring another model under its name."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config.get("overrides", {}))
    wrong = []
    for hf_key, field in HF_TO_REPO.items():
        if hf_key in config and getattr(cfg, field) != config[hf_key]:
            wrong.append(f"{hf_key}={config[hf_key]!r} but {field}="
                         f"{getattr(cfg, field)!r}")
    act = config.get("hidden_act")
    if act is not None and HIDDEN_ACT_TO_REPO.get(act) != cfg.gated_act:
        wrong.append(f"hidden_act={act!r} but gated_act={cfg.gated_act!r}")
    if config.get("qk_norm", False) != cfg.qk_norm:
        wrong.append(f"qk_norm={config.get('qk_norm', False)!r} but "
                     f"qk_norm={cfg.qk_norm!r}")
    if wrong:
        raise BenchError(f"configuration {config['registry']!r} does not run "
                         f"as its file states: {'; '.join(wrong)}")
    return cfg


# -- seeds --------------------------------------------------------------------

def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator for one use of the run's seed (traffic,
    sampling, ...).  Any whole number is a valid seed."""
    words = [int(b) for b in stream.encode()]
    return np.random.default_rng([int(seed) % 2**64, *words])


def weight_seed(seed: int) -> int:
    """The 31-bit seed of ``jax.random.PRNGKey`` for the model's weights
    (PRNGKey silently drops bits above 32)."""
    return int(rng(seed, "weights").integers(0, 2**31 - 1))


# -- host and device readings ------------------------------------------------

def process_age_s() -> float:
    """Seconds since this process started, from /proc (10 ms ticks)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])          # field 22: starttime
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def proc_status_kib(field: str) -> int | None:
    """A ``Vm*`` field of /proc/self/status in KiB; None where the kernel
    does not report it (the chip host's has no ``VmHWM``)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(field + ":"):
                return int(line.split()[1])
    return None


class RssSampler:
    """Peak resident set size over an interval, sampled every
    ``period_s`` on a thread of its own.  The kernel's own peak (VmHWM)
    cannot be reset where /proc/self/clear_refs is refused, as on the chip
    host, so the window's peak is sampled."""

    def __init__(self, period_s: float = 0.01) -> None:
        self.period_s = period_s
        self.peak_kib = 0
        self.samples = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="bench-rss", daemon=True)

    def _loop(self) -> None:
        while True:
            self.peak_kib = max(self.peak_kib, proc_status_kib("VmRSS"))
            self.samples += 1
            if self._stop.wait(self.period_s):
                return

    def start(self) -> "RssSampler":
        self._thread.start()
        return self

    def stop(self) -> int:
        self._stop.set()
        self._thread.join()
        self.peak_kib = max(self.peak_kib, proc_status_kib("VmRSS"))
        return self.peak_kib * 1024


class CompileCounter:
    """XLA compiles (persistent-cache reads included) while open, counted
    through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.compiles = 0
        self.seconds = 0.0
        self._open = False

    def _listen(self, event: str, seconds: float, **_kw) -> None:
        if self._open and event == self.EVENT:
            self.compiles += 1
            self.seconds += seconds

    def __enter__(self) -> "CompileCounter":
        import jax
        self._open = True
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        self._open = False
        jax.monitoring.unregister_event_duration_listener(self._listen)


def device_facts(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def device_peak_bytes(devices) -> int:
    """Peak device bytes in use on the fullest chip, as the runtime
    reports it (0 where the backend does not report)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def enable_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, so the first run of a cell there compiles and later runs
    read every program back.  Set in code, it takes the place of any
    ``JAX_COMPILATION_CACHE_DIR`` of the environment, which could be
    shared with another checkout."""
    import jax
    cache = root / RUN_DIR_NAME / "jax_cache"
    cache.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


class StoreDir:
    """The run's store root: ``bench/store.json``'s ``root`` (relative to
    the checkout) unless the cell's workload file names another, one
    directory per cell, cleared at start and removed at exit."""

    def __init__(self, cell: Cell) -> None:
        store = load_json(cell.root / "bench" / "store.json")
        base = cell.workload.get("store_root", store["root"])
        self.path = (cell.root / base / cell.name).resolve()

    def __enter__(self) -> Path:
        shutil.rmtree(self.path, ignore_errors=True)
        self.path.mkdir(parents=True)
        return self.path

    def __exit__(self, *exc) -> None:
        shutil.rmtree(self.path, ignore_errors=True)


class Window:
    """The measured window: opens after warm-up, closes when the driver
    says its last whole unit (step or wave) has ended.

    At open it takes ``setup_s`` (the process's age), starts the host
    memory sampler and the compile counter, and in a traced run the
    profiler and the ``bench.window`` annotation."""

    def __init__(self, trace_dir: Path | None) -> None:
        self.trace_dir = trace_dir
        self.setup_s = None
        self.t0 = None
        self.seconds = None
        self.host_peak_bytes = None
        self.rss_samples = None
        self.compiles = None
        self._sampler = None
        self._counter = None
        self._annotation = None

    def open(self) -> None:
        import jax
        self.setup_s = process_age_s()
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        self._counter = CompileCounter().__enter__()
        self._sampler = RssSampler().start()
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def close(self) -> None:
        import jax
        self.seconds = self.elapsed()
        self.host_peak_bytes = self._sampler.stop()
        self.rss_samples = self._sampler.samples
        self._counter.__exit__()
        self.compiles = self._counter.compiles
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()


def load_metric_reader(cell: Cell, name: str):
    """``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = cell.root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_driver(cell: Cell):
    path = cell.root / "bench" / "drivers" / f"{cell.driver}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_driver_" + cell.driver, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def sq_norm(x: np.ndarray, chunk: int = 1 << 22) -> float:
    """Squared Frobenius norm, summed in float64 chunk by chunk."""
    flat = np.ravel(x)
    total = 0.0
    for lo in range(0, flat.size, chunk):
        part = flat[lo:lo + chunk].astype(np.float64)
        total += float(np.dot(part, part))
    return total
