"""chip_smoke.py's train and serve phases, with every correctness check,
on the reduced Qwen2.5-0.5B config on the CPU.  Only ``main()``'s backend
gate needs the chip; here it must refuse."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen2.5-0.5b").reduced()


def test_train_phase_reduced(smoke, cfg, tmp_path):
    r = smoke.train_phase(cfg, tmp_path / "train")
    assert len(r["losses"]) == 3
    assert r["compiles"] > 0 and r["compile_s"] > 0
    assert r["tokens_per_s"] > 0 and r["steady_step_s"] > 0
    assert len(r["fetch_wait_s"]) == len(r["optim_gate_s"]) == 3
    assert r["peak_host_bytes"] > 0


def test_serve_phase_reduced(smoke, cfg, tmp_path):
    r = smoke.serve_phase(cfg, tmp_path / "serve")
    assert 0 <= r["logit_diff"] <= smoke.LOGIT_TOL
    assert r["decode_steps"] >= 31     # 32 tokens, the first from prefill
    assert r["tokens_per_s"] > 0 and r["peak_host_bytes"] > 0


def test_train_phase_fails_outside_loss_band(smoke, cfg, tmp_path,
                                             monkeypatch):
    monkeypatch.setattr(smoke, "LOSS_BAND_NATS", -1.0)
    with pytest.raises(smoke.SmokeFailure, match="train step 1: loss"):
        smoke.train_phase(cfg, tmp_path / "train", steps=1, seq=128)


def test_serve_phase_fails_past_logit_tolerance(smoke, cfg, tmp_path,
                                                monkeypatch):
    monkeypatch.setattr(smoke, "LOGIT_TOL", -1.0)
    with pytest.raises(smoke.SmokeFailure, match="cached logits differ"):
        smoke.serve_phase(cfg, tmp_path / "serve", new_tokens=2)


def test_device_check_names_the_wrong_platform(smoke):
    class Dev:
        platform = "elsewhere"

    with pytest.raises(smoke.SmokeFailure, match="elsewhere"):
        smoke.check_devices({Dev()}, "probe")
    with pytest.raises(smoke.SmokeFailure, match="no jitted output"):
        smoke.check_devices(frozenset(), "probe")


def test_main_refuses_the_cpu_backend(smoke, tmp_path, capsys):
    root = tmp_path / "store"
    assert smoke.main(["--store-root", str(root)]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out
    assert not root.exists()


def test_script_alone_fails_without_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
