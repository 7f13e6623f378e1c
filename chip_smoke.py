#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, at tinyllama-1.1b's full width.

    python chip_smoke.py               # one chip: kernels, train + resume, serve
    python chip_smoke.py --four-chips  # four chips: tp_fsdp step against one
                                       # device, elastic restore (2,2) -> (4,1)

Everything is generated from a seed; weights are random. Each phase prints
one JSON line of what it saw. These are smoke numbers (one short run, compile
included where noted), not benchmark metrics. Any failed check raises and
the process exits non-zero; nothing falls back to the CPU. Only when every
phase has passed does the last line read

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
ARCH = "tinyllama-1.1b"
TRAIN_BATCH, TRAIN_SEQ = 4, 512
SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW, SERVE_BATCH = 8, 128, 32, 4
BF16_TOL = 2e-2   # max |out - ref| relative to max |ref|


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "numbers": "smoke", **fields}),
          flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (a persistent-cache hit records no backend compile)."""

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration

    def since(self, mark: float) -> float:
        return round(self.total - mark, 3)


def require(ok: bool, what) -> None:
    """A failed check raises; unlike assert it survives python -O."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def max_rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    require(out.shape == ref.shape, (out.shape, ref.shape))
    require(np.isfinite(out).all(), "non-finite kernel output")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-6))


def timed(fn, *args, reps: int = 3) -> float:
    import jax
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------- one chip
def phase_kernels():
    """Both Pallas kernels compiled for the chip (no interpret mode) at real
    widths, against their jnp references evaluated in float32."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.ssd_scan import ssd_scan, ssd_scan_ref

    def check(name, fn, ref_fn, args, shape):
        fn = jax.jit(fn)
        hlo = fn.lower(*args).compile().as_text()
        require("tpu_custom_call" in hlo, f"{name}: no Pallas kernel in HLO")
        out = fn(*args)
        f32 = [a.astype(jnp.float32) for a in args]
        with jax.default_matmul_precision("float32"):
            ref = jax.jit(ref_fn)(*f32)
        err = [max_rel_err(o, r) for o, r in
               zip(jax.tree.leaves(out), jax.tree.leaves(ref))]
        require(max(err) < BF16_TOL, f"{name}: error {err} vs reference")
        emit("kernels", kernel=name, shape=shape, tpu_custom_call=True,
             max_rel_err=err, best_of_3_s=timed(fn, *args))

    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)
    normal = lambda i, shape, dt=jnp.bfloat16: jax.random.normal(
        keys[i], shape, jnp.float32).astype(dt)

    # tinyllama attention: H32 KV4 hd64, causal, S 2048
    B, S, H, KV, hd = 1, 2048, 32, 4, 64
    check("flash_attention",
          lambda q, k, v: flash_attention(q, k, v, True, 0, 512, 512),
          lambda q, k, v: attention_ref(q, k, v, causal=True),
          (normal(0, (B, S, H, hd)), normal(1, (B, S, KV, hd)),
           normal(2, (B, S, KV, hd))),
          dict(B=B, S=S, H=H, KV=KV, hd=hd, causal=True, dtype="bfloat16"))

    # mamba2-2.7b SSD: H80 P64 N128, chunk 256, S 2048
    b, nc, Q, H, P, N = 1, 8, 256, 80, 64, 128
    x = normal(3, (b, nc, Q, H, P)) * 0.5
    dt = jax.nn.softplus(normal(4, (b, nc, Q, H), jnp.float32)) * 0.1
    A = -jnp.exp(normal(5, (H,), jnp.float32) * 0.5)
    Bm, Cm = normal(6, (b, nc, Q, N), jnp.float32), normal(7, (b, nc, Q, N),
                                                          jnp.float32)
    check("ssd_scan", ssd_scan, ssd_scan_ref,
          (x, dt, Bm, Cm, dt * A, jnp.ones((H,), jnp.float32)),
          dict(b=b, S=nc * Q, H=H, P=P, N=N, chunk=Q, x_dtype="bfloat16"))


def phase_train(clock: CompileClock):
    """train() at full width, one asynchronous checkpoint through the
    I/O-aware runtime, then a second train() that resumes from it."""
    import math

    import jax
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.launch.train import train

    cfg = get_config(ARCH)
    ckpt = Path(tempfile.mkdtemp(prefix="chip_smoke_ckpt_"))
    try:
        # run 1: steps 0..3, async save after step 2 overlaps step 3
        mark = clock.since(0)
        out1 = train(cfg, steps=4, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                     ckpt_dir=str(ckpt), ckpt_every=3, resume=False,
                     seed=SEED)
        compile1 = clock.since(mark)
        del out1["params"], out1["opt_state"]   # free the device for run 2
        saved = CheckpointManager(ckpt).latest_step()
        manifest = ckpt / f"step_{saved:08d}" / "MANIFEST.json"
        require(saved == 2 and manifest.exists(), (saved, manifest))
        save_s = json.loads(manifest.read_text())["save_seconds"]

        # run 2: resume from the checkpoint, steps 3..5
        mark = clock.since(0)
        out2 = train(cfg, steps=6, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                     ckpt_dir=str(ckpt), ckpt_every=0, resume=True,
                     seed=SEED)
        compile2 = clock.since(mark)
        del out2["params"], out2["opt_state"]
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)

    losses = out1["losses"] + out2["losses"]
    require(all(math.isfinite(x) for x in losses), losses)
    require(out1["steps_run"] == 4 and len(out1["save_stall_s"]) == 1, out1)
    require(out2["start_step"] == saved + 1 and out2["steps_run"] == 3, out2)
    # step 3's loss is taken before its update, from the state after step 2:
    # in memory in run 1, restored from the checkpoint in run 2
    require(abs(out1["losses"][3] - out2["losses"][0]) < 1e-3, losses)
    stats = jax.devices()[0].memory_stats() or {}
    emit("train", arch=ARCH, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
         losses=losses, saved_step=saved, resumed_at=out2["start_step"],
         compile_s=[compile1, compile2],
         first_step_s_incl_compile=[out1["step_s"][0], out2["step_s"][0]],
         steady_step_s=statistics.median(out1["step_s"][1:]
                                         + out2["step_s"][1:]),
         save_stall_s=out1["save_stall_s"][0], save_commit_s=save_s,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))


def phase_serve(clock: CompileClock):
    from repro.configs import get_config
    from repro.launch.serve import serve

    cfg = get_config(ARCH)
    n, prompt, new, batch = (SERVE_REQUESTS, SERVE_PROMPT, SERVE_NEW,
                             SERVE_BATCH)
    mark = clock.since(0)
    out = serve(cfg, n_requests=n, prompt_len=prompt, max_new=new,
                batch=batch, seed=SEED)
    done = out["completions"]
    require(out["requests"] == n and len(done) == n, out["requests"])
    for row in done:
        toks = row["tokens"]
        require(len(toks) == new, row)
        require(all(0 <= t < cfg.vocab_size for t in toks), row)
    emit("serve", arch=ARCH, requests=n, prompt_len=prompt, max_new=new,
         batch=batch, compile_s=clock.since(mark),
         tokens_per_s_incl_compile=out["tokens_per_s"],
         p50_s=out["p50_s"], p99_s=out["p99_s"], wall_s=out["wall_s"])


# -------------------------------------------------------------- four chips
def phase_four_chips(clock: CompileClock):
    """tinyllama step on one device against the same step over a (2, 2)
    tp_fsdp mesh; then a checkpoint saved under (2, 2), restored onto
    (4, 1)."""
    import math

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_config
    from repro.core import IORuntime, RealBackend
    from repro.data import SyntheticCorpus
    from repro.distributed import mesh_context
    from repro.distributed.sharding import STRATEGIES
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import tree_shardings
    from repro.launch.train import build_cluster, make_train_step
    from repro.models import Model
    from repro.optim import AdamWConfig, AdamWState, adamw_init

    cfg = get_config(ARCH)
    model = Model(cfg)
    step = make_train_step(model, AdamWConfig())
    batch = SyntheticCorpus(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH,
                            seed=SEED).batch(0)

    def spread(tree):
        """(devices holding a shard, largest share of the bytes on one)."""
        per_dev = {}
        for leaf in jax.tree.leaves(tree):
            for s in leaf.addressable_shards:
                per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
        return len(per_dev), max(per_dev.values()) / sum(per_dev.values())

    # one device: run the step, keep the results on the host, free the chip
    mark = clock.since(0)
    params, axes = model.init(jax.random.PRNGKey(SEED))
    host_params = jax.device_get(params)
    _, _, loss1, gnorm1 = step(params, adamw_init(params), batch)
    loss1, gnorm1 = float(loss1), float(gnorm1)
    del params
    compile1 = clock.since(mark)

    # the same step over a (2, 2) mesh, tp_fsdp rules
    mark = clock.since(0)
    mesh = make_mesh((2, 2), ("data", "model"))
    with mesh_context(mesh, rules=STRATEGIES["tp_fsdp"]):
        sh = tree_shardings(jax.eval_shape(lambda: host_params), axes, mesh)
        params = jax.device_put(host_params, sh)
        opt_sh = AdamWState(sh, sh, NamedSharding(mesh, P()))
        opt_state = jax.jit(adamw_init, out_shardings=opt_sh)(params)
        b = jax.device_put(batch, NamedSharding(mesh, P("data")))
        params, _, loss4, gnorm4 = step(params, opt_state, b)
        loss4, gnorm4 = float(loss4), float(gnorm4)
        n_dev, top_share = spread(params)
    compile4 = clock.since(mark)
    require(n_dev == 4 and top_share < 0.5, (n_dev, top_share))
    require(abs(loss4 - loss1) <= BF16_TOL * abs(loss1), (loss1, loss4))
    require(abs(gnorm4 - gnorm1) <= 5 * BF16_TOL * abs(gnorm1),
            (gnorm1, gnorm4))
    emit("four_chips_step", arch=ARCH, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
         mesh=[2, 2], loss_one_device=loss1, loss_mesh=loss4,
         gnorm_one_device=gnorm1, gnorm_mesh=gnorm4, devices_holding=n_dev,
         largest_device_share=round(top_share, 4),
         compile_s=[compile1, compile4])

    # elastic restore: save under (2, 2), restore onto (4, 1)
    saved_host = jax.device_get(params)
    ckpt = Path(tempfile.mkdtemp(prefix="chip_smoke_elastic_"))
    try:
        mgr = CheckpointManager(ckpt, n_shards=8)
        with IORuntime(build_cluster(), backend=RealBackend()):
            t0 = time.perf_counter()
            started = mgr.save(1, params)
            stall = time.perf_counter() - t0
            mgr.wait()
        require(started, "the save was skipped")
        del params, opt_state
        mesh_b = make_mesh((4, 1), ("data", "model"))
        with mesh_context(mesh_b, rules=STRATEGIES["tp_fsdp"]):
            sh_b = tree_shardings(jax.eval_shape(lambda: host_params), axes,
                                  mesh_b)
            t0 = time.perf_counter()
            restored, at = mgr.restore(host_params, shardings=sh_b)
            restore_s = time.perf_counter() - t0
            require(at == 1, at)
            n_dev_b, top_share_b = spread(restored)
            require(n_dev_b == 4 and top_share_b < 0.5, (n_dev_b, top_share_b))
            same = jax.tree.map(
                lambda a, b: np.asarray(a).tobytes() == np.asarray(b).tobytes(),
                saved_host, jax.device_get(restored))
            require(all(jax.tree.leaves(same)), "restored values differ")
            b = jax.device_put(batch, NamedSharding(mesh_b, P("data")))
            loss_b = float(jax.jit(model.loss)(restored, b))
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    require(math.isfinite(loss_b), loss_b)
    emit("four_chips_elastic", arch=ARCH, saved_mesh=[2, 2],
         restored_mesh=[4, 1], bit_identical=True, devices_holding=n_dev_b,
         largest_device_share=round(top_share_b, 4), loss_after=loss_b,
         save_stall_s=stall, restore_s=restore_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
                 "this smoke never runs on another platform")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} chips, JAX found {len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    clock = CompileClock()
    emit("setup", platform=devices[0].platform, kind=devices[0].device_kind,
         count=len(devices), compile_cache=cache_dir)

    if args.four_chips:
        phase_four_chips(clock)
    else:
        phase_kernels()
        phase_train(clock)
        phase_serve(clock)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
