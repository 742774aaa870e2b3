"""CPU tests of the benchmark harness.  Run them by hand from the root of
the checkout (the repo's own test run collects ``tests/`` only):

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# A two-layer model of 128 wide over a 512-token vocabulary, run through
# the same registry entry with overrides: small enough for the CPU.
TINY_CONFIG = {
    "overrides": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 2, "d_ff": 256, "vocab": 512,
                  "head_dim": 32},
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "eos_token_id": 511,
}
CELLS = {"finetune": "qwen2.5-0.5b.finetune",
         "batch_serve": "qwen3-4b-l4.batch-serve"}


def copy_benchmark(dest: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` copied to ``dest``, with a peak
    entry for the CPU so that the harness runs there."""
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    peaks_path = dest / "bench" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12}
    peaks_path.write_text(json.dumps(peaks))
    return dest


def tiny_cell(root: Path, driver: str):
    """The benchmark's cell of ``driver`` at the tiny size: the same
    files, driver and limits, with a small model and short traffic."""
    from bench import harness
    cell = harness.load_cell(CELLS[driver], root)
    cell.config.update(TINY_CONFIG)
    if driver == "finetune":
        cell.traffic.update(batch=2, seq=64)
        cell.traffic["doc_len"].update(median=16, min=4, max=128)
    else:
        cell.traffic.update(wave_size=4, new_tokens=4)
        cell.traffic["prompt_len"].update(median=20, min=4, max=90)
        cell.workload.update(decode={"batch": 4, "max_seq": 128,
                                     "bucket": 32}, sample_requests=6)
    return cell


@pytest.fixture
def bench_copy(tmp_path):
    return copy_benchmark(tmp_path / "checkout")


@pytest.fixture(scope="session")
def cpu_devices():
    import jax
    devices = jax.devices()
    if devices[0].platform != "cpu":
        pytest.skip("the harness tests run on the CPU (JAX_PLATFORMS=cpu)")
    return devices
