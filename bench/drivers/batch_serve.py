"""Driver ``batch_serve``: an offline batch job through
``ServingEngine.run`` over an ``OffloadedDecoder``, continuous mode.

Waves of requests run back to back, each one ``ServingEngine.run`` over
the wave.  Every wave of a mix has the same prompt lengths, so the warm-up
wave compiles every prompt bucket and decode extent the window will use.
The window opens after it and runs whole waves until one ends at or after
``--seconds``.

After the window: a sample of the window's requests drawn from the seed,
the one with the longest prompt always in it, goes to the reference, which
runs the full forward pass over each prompt with its served tokens and
reads how far each served token's logit lies below its best.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import compare, flops, reference, traffic_gen
from repro.core import OffloadPolicy
from repro.core.model_adapter import make_offloadable_lm
from repro.serve import DecodeSpec, OffloadedDecoder, Request, ServingEngine


def _sample(served: list, rng: np.random.Generator, n: int) -> list:
    """``n`` of the served (prompt, output) pairs, the longest prompt
    always among them."""
    longest = max(range(len(served)), key=lambda i: len(served[i][0]))
    rest = [i for i in range(len(served)) if i != longest]
    pick = rng.permutation(rest)[:max(0, n - 1)]
    return [served[i] for i in [longest, *sorted(pick)]]


def check_served(sizes, seed: int, sample: list, batch: int, max_seq: int,
                 *, matmul: str = "fp32") -> list[float]:
    """Per served token, the reference's best logit minus the served
    token's, at that position of ``prompt + output``.  With ``matmul``
    other than fp32 the served token is the one that precision puts first
    (the control)."""
    params = reference.init_weights(sizes, seed)
    gaps: list[float] = []
    for lo in range(0, len(sample), batch):
        group = sample[lo:lo + batch]
        k = max(len(out) for _p, out in group)
        tokens = np.zeros((batch, max_seq), np.int32)
        pos = np.zeros((batch, k), np.int32)
        for r, (prompt, out) in enumerate(group):
            seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
            tokens[r, :len(seq)] = seq
            pos[r] = len(prompt) - 1 + np.minimum(np.arange(k), len(out) - 1)
        ref = reference.logits_at(sizes, params, tokens, pos)
        if matmul != "fp32":
            low = reference.logits_at(sizes, params, tokens, pos,
                                      matmul=matmul)
        for r, (_prompt, out) in enumerate(group):
            chosen = (np.asarray(out, np.int64) if matmul == "fp32"
                      else low[r, :len(out)].argmax(-1))
            gaps += compare.logit_gaps(ref[r, :len(out)], chosen)
    return gaps


def run(ctx) -> dict:
    cfg, wl, mix = ctx.cfg, ctx.cell.workload, ctx.cell.traffic
    waves = traffic_gen.request_waves(mix, ctx.rng("traffic"),
                                      vocab=cfg.vocab, eos=ctx.eos)
    new_tokens = mix["new_tokens"]
    ctx.log_rss("before the model")
    model = make_offloadable_lm(cfg, jax.random.PRNGKey(ctx.weight_seed))
    ctx.log_rss("after the model's weights")
    policy = (OffloadPolicy.preset(wl["policy"]).with_store(str(ctx.store))
              .with_overlap(wl["overlap"]).build())
    spec = DecodeSpec(**wl["decode"])
    records: list[dict] = []
    served: list = []
    with OffloadedDecoder(model, policy, decode=spec) as dec:
        engine = ServingEngine(dec)
        sess = dec.session

        def wave(tag: str) -> None:
            prompts = next(waves)
            reqs = [Request(rid=f"{tag}-{i}", prompt=p,
                            max_new_tokens=new_tokens)
                    for i, p in enumerate(prompts)]
            o0, io0 = sess.overlap_snapshot(), sess.store.stats.snapshot()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.wave"):
                report = engine.run(reqs)
            wall = time.perf_counter() - t0
            o1, io1 = sess.overlap_snapshot(), sess.store.stats.snapshot()
            done = [r for r in report.requests
                    if r.state.value == "done"
                    and len(r.output) == new_tokens]
            records.append({
                "tag": tag, "wave_s": wall, "requests": len(reqs),
                "completed": len(done), "tokens": report.total_tokens,
                "prefills": report.prefills,
                "decode_steps": report.decode_steps,
                "prompt_lens": [int(len(p)) for p in prompts],
                "fetch_wait_s": o1["fetch_seconds"] - o0["fetch_seconds"],
                "store_read_bytes": io1["bytes_read"] - io0["bytes_read"],
                "store_written_bytes": (io1["bytes_written"]
                                        - io0["bytes_written"]),
            })
            if tag != "warm":
                served.extend((r.prompt, list(r.output)) for r in done)

        ctx.log_rss("with the session open")
        wave("warm")
        ctx.log_rss("after warm-up")
        ctx.window.open()
        n = 0
        while True:
            wave(f"w{n}")
            n += 1
            if ctx.window.elapsed() >= ctx.seconds:
                break
        ctx.window.close()
        ctx.read_device_peak()
        tracker_peak = sess.tracker.peak_allocated
    del model, dec, engine, sess
    gc.collect()

    window = records[1:]
    for r in records:
        ctx.log(f"wave {r['tag']}: {r['wave_s']:.4f} s, {r['tokens']} "
                f"tokens, {r['prefills']} prefills + {r['decode_steps']} "
                f"decode steps, fetch_wait_s {r['fetch_wait_s']:.4f}, store "
                f"read {r['store_read_bytes']} B, written "
                f"{r['store_written_bytes']} B")

    t0 = time.perf_counter()
    sizes = reference.Sizes(ctx.cell.config)
    sample = _sample(served, ctx.rng("sample"), wl["sample_requests"])
    gaps = check_served(sizes, ctx.weight_seed, sample, spec.batch,
                        spec.max_seq)
    checks = compare.serve_checks(gaps, wl["limits"])
    ctx.log(f"reference: {len(sample)} requests, {len(gaps)} served tokens "
            f"in {time.perf_counter() - t0:.3f} s")

    window_s = ctx.window.seconds
    tokens = sum(r["tokens"] for r in window)
    model_flops = 0
    for r in window:
        for p in r["prompt_lens"]:
            model_flops += flops.prefill_flops(ctx.cell.config, p)
            model_flops += sum(flops.decode_flops(ctx.cell.config, p + j)
                               for j in range(new_tokens - 1))
    return {
        "attempted": sum(r["requests"] for r in window),
        "failed": sum(r["requests"] - r["completed"] for r in window),
        "checks": checks,
        "e2e": {"serve_tokens_per_s": tokens / window_s},
        "window_waves": window,
        "window_s": window_s,
        "window_tokens": tokens,
        "weight_passes": sum(r["prefills"] + r["decode_steps"]
                             for r in window),
        "fetch_wait_s": sum(r["fetch_wait_s"] for r in window),
        "store_read_bytes": sum(r["store_read_bytes"] for r in window),
        "store_written_bytes": sum(r["store_written_bytes"]
                                   for r in window),
        "tracker_peak_bytes": tracker_peak,
        "model_flops": model_flops,
        "sample": sample,
    }
