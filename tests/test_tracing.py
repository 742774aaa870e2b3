"""Spans and busy counters of the host pipeline (``OverlapStats.timed``):
every leg the benchmark's per-layer metrics read is counted per
``train_step``, spanned on the profiler's host planes, and read back by
its metric reader."""

import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ModelConfig
from repro.core import DecodeSpec, OffloadPolicy, OffloadSession
from repro.core.model_adapter import make_offloadable_lm
from repro.core.overlap import SPANS, OverlapStats
from repro.data import DataLoader, SyntheticTextDataset
from repro.serve import OffloadedDecoder

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)
METRICS = Path(__file__).resolve().parent.parent / "bench" / "metrics"
BUSY_KEYS = ("adam_read_s", "adam_arena_wait_s", "adam_update_s",
             "adam_commit_wait_s", "adam_write_s", "grad_d2h_s",
             "h2d_copy_s")
STORE_SPANS = {"store.read", "store.write"}


def _model():
    return make_offloadable_lm(CFG, jax.random.PRNGKey(0))


def _batches(n):
    dl = DataLoader(SyntheticTextDataset(vocab=256, seed=1), batch=4,
                    seq_len=32)
    return [dl.next_batch() for _ in range(n)]


def _policy(root, overlap):
    return (OffloadPolicy.preset("memascend").with_store(root)
            .with_adam(lr=3e-3).with_overlap(overlap).build())


def _host_span_names(trace_dir) -> set[str]:
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    names = set()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def test_timed_counts_the_span_interval_even_when_the_work_raises():
    stats = OverlapStats()
    with stats.timed("adam_update_seconds"):
        pass
    first = stats.snapshot()["adam_update_seconds"]
    with pytest.raises(ValueError), stats.timed("adam_update_seconds"):
        raise ValueError("fails inside the span")
    assert 0 < first < stats.snapshot()["adam_update_seconds"]
    assert not any(s.startswith("bench.") for s in SPANS.values())
    # every busy counter is a train_step key; optim_prefetch_wait_s too
    assert {n.removesuffix("_seconds") + "_s" for n in SPANS} == \
        {*BUSY_KEYS, "optim_prefetch_wait_s"}


@pytest.mark.parametrize("overlap", ["full", "sync"])
def test_train_step_reports_busy_counters(tmp_store_root, overlap):
    steps = []
    with OffloadSession(_model(), _policy(tmp_store_root, overlap)) as s:
        for b in _batches(2):
            steps.append(s.train_step(b["tokens"], b["labels"]))
    for m in steps:
        for key in BUSY_KEYS:
            assert m[key] >= 0, (key, m[key])
    # under full overlap step 1's Adam lands inside step 2's window
    for key in ("adam_read_s", "adam_update_s", "adam_write_s"):
        assert sum(m[key] for m in steps) > 0, key


def test_train_step_trace_holds_every_span(tmp_store_root, tmp_path):
    b1, b2 = _batches(2)
    with OffloadSession(_model(), _policy(tmp_store_root, "full")) as s:
        s.train_step(b1["tokens"], b1["labels"])
        with jax.profiler.trace(str(tmp_path)):
            s.train_step(b2["tokens"], b2["labels"])
            s.synchronize()
    names = _host_span_names(tmp_path)
    assert set(SPANS.values()) | STORE_SPANS <= names
    assert not any(n.startswith("bench.") for n in names)


def test_offloaded_decode_trace_holds_store_and_h2d_spans(tmp_store_root,
                                                          tmp_path):
    prompts = np.random.default_rng(0).integers(3, CFG.vocab, size=(2, 6),
                                                dtype=np.int32)
    with jax.profiler.trace(str(tmp_path)):
        with OffloadedDecoder(_model(), _policy(tmp_store_root, "full"),
                              decode=DecodeSpec(batch=2, max_seq=32,
                                                bucket=8)) as dec:
            dec.generate(prompts, 4)
    names = _host_span_names(tmp_path)
    assert STORE_SPANS | {SPANS["h2d_copy_seconds"]} <= names
    assert not any(n.startswith("bench.") for n in names)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("key", BUSY_KEYS)
def test_metric_reader_takes_the_window_mean(key):
    read = _reader(f"{key}.train")
    steps = [{key: 1.5, "optim_gate_s": 9.0}, {key: 2.5, "optim_gate_s": 9.0}]
    assert read({"window_steps": steps}) == pytest.approx(2.0)
    serve = {"window_waves": [{"wave_s": 40.0}], "fetch_wait_s": 38.0,
             "weight_passes": 21}
    assert read(serve) is None
    # a program without the counter: the reader finds nothing, and no error
    assert read({"window_steps": [{"optim_gate_s": 9.0}]}) is None


def test_train_step_counts_the_entries_adam_updated(tmp_store_root):
    b = _batches(1)[0]
    with OffloadSession(_model(), _policy(tmp_store_root, "sync")) as s:
        m = s.train_step(b["tokens"], b["labels"])
        total = sum(meta.size for meta in s.optimizer.subgroups.values())
    assert m["applied"]
    assert m["adam_update_elems"] == total
    assert m["adam_parallel_elems"] == 0    # every tiny tensor is one tile


def test_ns_per_elem_reader_divides_the_window_sums():
    read = _reader("adam_update_ns_per_elem.train")
    steps = [{"adam_update_s": 1.0, "adam_update_elems": 10 ** 8},
             {"adam_update_s": 3.0, "adam_update_elems": 3 * 10 ** 8}]
    assert read({"window_steps": steps}) == pytest.approx(10.0)
    # a program without the counter, or a window that updated nothing
    assert read({"window_steps": [{"adam_update_s": 1.0}]}) is None
    assert read({"window_steps": [{"adam_update_s": 0.0,
                                   "adam_update_elems": 0}]}) is None
    assert read({"window_waves": [{"wave_s": 40.0}]}) is None
