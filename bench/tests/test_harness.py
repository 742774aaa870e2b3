"""The harness end to end below its look for a chip, at a tiny size on the
CPU: both drivers, the traffic, the trace reduction, the FLOP counts, the
finding of files by name, the refusal of an unknown device, the control
and the faults the output check has to catch."""

from __future__ import annotations

import hashlib
import json
import math
from pathlib import Path

import numpy as np
import pytest
from conftest import CELLS, TINY_CONFIG, tiny_cell

from bench import compare, flops, harness, reference, trace, traffic_gen

DATA = Path(__file__).resolve().parent / "data"
WIDE_CONFIG = {
    "overrides": {"n_layers": 2, "d_model": 512, "n_heads": 8,
                  "n_kv_heads": 2, "d_ff": 1024, "vocab": 2048,
                  "head_dim": 64},
    "hidden_size": 512, "intermediate_size": 1024, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 64,
    "vocab_size": 2048, "eos_token_id": 2047,
}


def _run(cell, devices, seed=2**31 + 17, seconds=1.0):
    from bench import run
    return run.run_cell(cell, seed, seconds, False, devices)


@pytest.mark.parametrize("driver", sorted(CELLS))
def test_driver_end_to_end(bench_copy, cpu_devices, driver):
    from bench import run
    cell = tiny_cell(bench_copy, driver)
    ctx, record = run.run_driver(cell, 2**33 + 5, 1.0, None, cpu_devices)
    assert record["attempted"] > 0 and record["failed"] == 0
    assert compare.passed(record["checks"]), record["checks"]
    assert ctx.window.compiles == 0
    assert ctx.window.setup_s > 0 and ctx.window.host_peak_bytes > 0
    # every per-layer metric of the cell finds something to read
    record.update(peak_flops_per_s=1e12, chips=1,
                  trace={"busy_s": 0.25, "window_s": 1.0})
    values = run.per_layer_metrics(cell, record)
    assert set(values) == {m["name"] for m in cell.metrics("per_layer")}
    for name, v in values.items():
        assert math.isfinite(v["value"]) and v["value"] >= 0, name
    idle = "device_idle_pct." + ("train" if driver == "finetune" else "serve")
    assert values[idle]["value"] == 75.0
    result = run.run_cell(cell, 7, 1.0, False, cpu_devices)
    assert result["correct"] is True
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert set(result["metrics"]) == {m["name"]
                                      for m in cell.metrics("end_to_end")}


def test_traffic_is_the_seeds(bench_copy):
    cell = harness.load_cell(CELLS["finetune"], bench_copy)

    def batches(seed, n=2):
        gen = traffic_gen.packed_batches(cell.traffic, harness.rng(seed, "t"),
                                         vocab=151936, eos=151643)
        return [next(gen) for _ in range(n)]

    a, b, c = batches(2**40 + 3), batches(2**40 + 3), batches(2**40 + 4)
    for (ta, la), (tb, lb) in zip(a, b, strict=True):
        assert np.array_equal(ta, tb) and np.array_equal(la, lb)
    assert not np.array_equal(a[0][0], c[0][0])
    tokens, labels = a[0]
    assert tokens.shape == (4, 2048) and np.array_equal(tokens[:, 1:],
                                                        labels[:, :-1])
    assert not np.array_equal(a[0][0], a[1][0])       # every row differs

    serve = harness.load_cell(CELLS["batch_serve"], bench_copy)

    def waves(seed):
        gen = traffic_gen.request_waves(serve.traffic, harness.rng(seed, "t"),
                                        vocab=151936, eos=151645)
        return [next(gen) for _ in range(2)]

    wa, wb, wc = waves(11), waves(11), waves(12)
    assert all(np.array_equal(x, y) for u, v in zip(wa, wb, strict=True)
               for x, y in zip(u, v, strict=True))
    assert not all(np.array_equal(x, y) for x, y in zip(wa[0], wc[0],
                                                        strict=True))
    # every seed sends the same prompt lengths, in another order
    lengths = traffic_gen.wave_lengths(serve.traffic)
    for w in wa + wc:
        assert sorted(len(p) for p in w) == lengths
    assert lengths[0] >= 32 and lengths[-1] == 960


def test_trace_reduction_on_a_chip_trace():
    got = trace.reduce_trace(DATA / "small.xplane.pb")
    assert got["devices"] == 1
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["device_ops"] and all(name.startswith("jit_f/")
                                     for name, _s in got["device_ops"])
    op_total = sum(s for _n, s in got["device_ops"])
    assert op_total == pytest.approx(got["busy_s"], rel=0.05)
    assert got["idle_gaps"]
    longest = got["idle_gaps"][0]
    assert longest[1] >= 0.015        # the 20 ms host sleep in each step
    assert longest[0].startswith("bench.train_step")
    gaps = [s for _label, s in got["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_flops_hand_count():
    config = json.loads((Path(harness.BENCH) / "configs" /
                         "qwen2.5-0.5b.json").read_text())
    # per layer: q 896x896, k and v 896x128, o 896x896, gate/up/down
    # 3 x 896x4864; the head 896x151936
    per_layer = 896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    matmul = 24 * per_layer + 896 * 151936
    attention = 4 * 14 * 64 * (2048 + 1) / 2 * 24
    assert flops.forward_flops_per_token(config, 2048) == \
        2 * matmul + attention
    assert flops.train_flops_per_token(config, 2048) == \
        pytest.approx(3.228137472e9)
    assert flops.decode_flops(config, 0) == 2 * matmul + 4 * 896 * 24
    assert flops.prefill_flops(config, 1) == \
        2 * 24 * per_layer + 2 * 896 * 151936 + 4 * 896 * 24


def _code_digest(root: Path) -> str:
    h = hashlib.sha256()
    for p in sorted((root / "bench").rglob("*.py")):
        h.update(p.relative_to(root).as_posix().encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def test_a_new_cell_is_found_by_name(bench_copy, cpu_devices):
    before = _code_digest(bench_copy)
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "tiny-dense.finetune", "config": "tiny-dense",
        "traffic": "packed-docs-2x64", "chips": 1,
        "why": "a cell added as data files only"})
    spec["end_to_end"][0]["workloads"].append("tiny-dense.finetune")
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = bench_copy / "bench"
    config = json.loads((bench / "configs" / "qwen2.5-0.5b.json")
                        .read_text())
    config.update(TINY_CONFIG)
    (bench / "configs" / "tiny-dense.json").write_text(json.dumps(config))
    (bench / "traffic" / "packed-docs-2x64.json").write_text(json.dumps({
        "kind": "packed_documents", "batch": 2, "seq": 64,
        "doc_len": {"dist": "lognormal", "median": 16, "sigma": 1.0,
                    "min": 4, "max": 128}}))
    workload = json.loads((bench / "workloads" /
                           "qwen2.5-0.5b.finetune.json").read_text())
    (bench / "workloads" / "tiny-dense.finetune.json").write_text(
        json.dumps(workload))
    assert _code_digest(bench_copy) == before
    cell = harness.load_cell("tiny-dense.finetune", bench_copy)
    assert cell.driver == "finetune" and cell.traffic["seq"] == 64
    result = _run(cell, cpu_devices)
    assert result["correct"] is True
    assert "train_tokens_per_s" in result["metrics"]


def test_unknown_device_kind_is_refused(bench_copy, cpu_devices):
    peaks_path = bench_copy / "bench" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    del peaks["devices"]["cpu"]
    peaks_path.write_text(json.dumps(peaks))
    with pytest.raises(harness.BenchError, match="peaks.json"):
        _run(tiny_cell(bench_copy, "finetune"), cpu_devices)


def test_a_registry_that_drifts_from_the_file_is_refused(bench_copy):
    cell = tiny_cell(bench_copy, "finetune")
    cell.config["rope_theta"] = 1e6
    with pytest.raises(harness.BenchError, match="rope_theta"):
        harness.model_config(cell.config)


def test_the_run_needs_a_chip(bench_copy, cpu_devices, capsys):
    from bench import run
    assert run.main(["--workload", CELLS["finetune"], "--seed", "1",
                     "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert "{" not in out.out


# -- the control and the faults the check has to catch ------------------------

def test_training_control_fails(bench_copy, cpu_devices):
    """The reference computed in float8 in the program's place fails one
    of the training cell's numbers, at the tiny size."""
    cell = tiny_cell(bench_copy, "finetune")
    sizes = reference.Sizes(cell.config)
    gen = traffic_gen.packed_batches(cell.traffic, harness.rng(3, "t"),
                                     vocab=512, eos=511)
    batches = [next(gen) for _ in range(3)]
    adam = {"lr": cell.workload["lr"], "beta1": 0.9, "beta2": 0.999,
            "eps": 1e-8}
    ref = reference.train(sizes, 5, batches, adam)
    low = reference.train(sizes, 5, batches, adam, matmul="fp8")
    checks = compare.train_checks(low, ref, cell.workload["limits"])
    assert not compare.passed(checks), checks


def test_serving_control_fails(bench_copy, cpu_devices):
    """The float8 reference, reading the gap of the token it puts first at
    each served position, fails the serving limit.  The logits' scale
    grows with the width, so this runs 512 wide (the tiny size's logits
    are too small for the cell's limit)."""
    from bench import run
    from bench.drivers import batch_serve
    cell = tiny_cell(bench_copy, "batch_serve")
    cell.config.update(WIDE_CONFIG)
    cell.traffic["new_tokens"] = 8
    cell.workload["sample_requests"] = 16
    ctx, record = run.run_driver(cell, 21, 1.0, None, cpu_devices)
    assert compare.passed(record["checks"]), record["checks"]
    decode = cell.workload["decode"]
    gaps = batch_serve.check_served(
        reference.Sizes(cell.config), ctx.weight_seed, record["sample"],
        decode["batch"], decode["max_seq"], matmul="fp8")
    checks = compare.serve_checks(gaps, cell.workload["limits"])
    assert not compare.passed(checks), checks


def _adam_noop(monkeypatch):
    import repro.core.optimizer as opt
    monkeypatch.setattr(opt, "adam_update", lambda *a, **k: None)


def _half_batch(monkeypatch):
    from repro.core.session import OffloadSession
    step = OffloadSession.train_step

    def half(self, tokens, labels):
        labels = np.array(labels)
        labels[labels.shape[0] // 2:] = -100
        return step(self, tokens, labels)

    monkeypatch.setattr(OffloadSession, "train_step", half)


def _token_altered(monkeypatch):
    from repro.serve.scheduler import ServingEngine
    emit = ServingEngine._emit

    def altered(self, r, token, now, next_tok, max_seq):
        if r.metrics.tokens_out == 1:
            token = (token + 1) % 500
        return emit(self, r, token, now, next_tok, max_seq)

    monkeypatch.setattr(ServingEngine, "_emit", altered)


@pytest.mark.parametrize("driver,fault", [
    ("finetune", _adam_noop),
    ("finetune", _half_batch),
    ("batch_serve", _token_altered),
])
def test_a_broken_timed_path_is_not_correct(bench_copy, cpu_devices,
                                            monkeypatch, driver, fault):
    fault(monkeypatch)
    result = _run(tiny_cell(bench_copy, driver), cpu_devices)
    assert result["correct"] is False, result["checks"]
