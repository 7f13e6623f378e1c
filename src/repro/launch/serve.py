"""Batched serving driver (deliverable b): continuous-batching-style loop —
prefill new requests, decode the active batch one token per step, retire
finished sequences, measure tokens/s. Request arrivals and trace dumps run
through the I/O-aware runtime (reads/log-writes are I/O tasks overlapping
the decode compute, the paper's serving-side analogue).

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_config, get_smoke_config
from ..core import Cluster, IORuntime, RealBackend, StorageDevice, WorkerNode, io, task
from ..models import Model
from ..obs.report import percentile, span_latencies
from .compile_cache import configure_compile_cache


@io
@task(returns=1)
def _dump_trace(path, record, prev=None):
    # `prev` is the previous dump's future: chaining it serializes appends
    # to the shared trace file (unordered writers on one path is exactly
    # lint diagnostic IO301 — and a real interleaving hazard on the
    # RealBackend's I/O thread pool)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
    return path


def serve(cfg, *, n_requests=8, prompt_len=32, max_new=16, batch=4,
          trace_path=None, seed=0):
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]

    prefill = jax.jit(lambda p, b: model.prefill(p, b, prompt_len + max_new))
    decode = jax.jit(model.decode_step)

    dev = StorageDevice(name="trace-fs", bandwidth=500, per_stream_cap=125)
    cluster = Cluster(workers=[WorkerNode(name="h0", cpus=2, io_executors=4,
                                          storage=dev)])
    done, t0 = [], time.monotonic()
    new_tokens = 0
    trace_tok = None
    lat = []
    with IORuntime(cluster, backend=RealBackend(), trace=True) as rt:
        rec = rt.trace()  # None under repro.lint's capture mode
        now = rec.now if rec is not None else (lambda: time.monotonic() - t0)
        queue = list(enumerate(prompts))
        while queue:
            wave, queue = queue[:batch], queue[batch:]
            admit = {rid: now() for rid, _ in wave}
            toks = jnp.asarray(np.stack([p for _, p in wave]))
            logits, state = prefill(params, {"tokens": toks})
            out = [[] for _ in wave]
            nxt = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)
            first_tok = {}
            for step in range(max_new):
                for i, (rid, _) in enumerate(wave):
                    out[i].append(int(nxt[i]))
                    if rid not in first_tok:
                        first_tok[rid] = now()
                logits, state = decode(params, state, nxt)
                nxt = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)
                new_tokens += len(wave)
            for (rid, _), o in zip(wave, out):
                t_end = now()
                lat.append(t_end - admit[rid])
                row = {"request": rid, "tokens": o,
                       "t": time.monotonic() - t0}
                done.append(row)
                if rec is not None:
                    # admission -> first-token -> finish span; the span
                    # event *is* the JSONL trace row, so the dumped file
                    # and the recorder's stream stay one schema
                    row = rec.span(
                        f"req-{rid}", cat="request", t0=admit[rid],
                        t1=t_end, request=rid, n_tokens=len(o),
                        first_token_s=first_tok[rid] - admit[rid])
                if trace_path:
                    trace_tok = _dump_trace(trace_path, row, trace_tok)
        if rec is not None:
            lat = span_latencies(rec, cat="request")
    wall = time.monotonic() - t0
    return {"requests": len(done), "new_tokens": new_tokens,
            "tokens_per_s": new_tokens / wall, "wall_s": wall,
            "p50_s": percentile(lat, 0.50), "p99_s": percentile(lat, 0.99),
            "completions": done}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--trace", default=None)
    args = ap.parse_args(argv)
    configure_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    out = serve(cfg, n_requests=args.requests, prompt_len=args.prompt_len,
                max_new=args.max_new, batch=args.batch, trace_path=args.trace)
    print(f"[serve] {out['requests']} requests, {out['new_tokens']} tokens, "
          f"{out['tokens_per_s']:.1f} tok/s, wall {out['wall_s']:.1f}s, "
          f"latency p50 {out['p50_s']:.3f}s p99 {out['p99_s']:.3f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
