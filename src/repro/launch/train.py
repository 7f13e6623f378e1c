"""End-to-end training driver (deliverable b).

Wires every substrate together: model zoo + AdamW + synthetic data pipeline
+ the I/O-aware runtime for async checkpointing (auto-constrained shard
writes overlapping train steps), resume-from-latest, SIGTERM preemption
save, and optional baseline mode (--io-aware=off: synchronous checkpoints,
the paper's non-I/O-aware baseline).

  PYTHONPATH=src python -m repro.launch.train --preset 20m --steps 50 \
      --ckpt-dir /tmp/ck --ckpt-every 10
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke
"""
from __future__ import annotations

import argparse
import functools
import json
import signal
import sys
import time

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointManager
from ..configs import ARCHS, get_config, get_smoke_config
from ..configs.base import ModelConfig
from ..core import Cluster, IORuntime, RealBackend, StorageDevice, WorkerNode
from ..data import PrefetchLoader, SyntheticCorpus
from ..models import Model
from ..optim import AdamWConfig, adamw_init, adamw_update
from .compile_cache import configure_compile_cache

PRESETS = {
    # ~100M-class model for real-hardware runs; smaller ones for CPU demos
    "100m": ModelConfig(name="repro-100m", family="dense", n_layers=12,
                        d_model=768, n_heads=12, n_kv_heads=12, d_ff=3072,
                        vocab_size=32000, remat=False),
    "20m": ModelConfig(name="repro-20m", family="dense", n_layers=6,
                       d_model=384, n_heads=6, n_kv_heads=6, d_ff=1536,
                       vocab_size=8192, remat=False),
    "5m": ModelConfig(name="repro-5m", family="dense", n_layers=4,
                      d_model=256, n_heads=4, n_kv_heads=4, d_ff=768,
                      vocab_size=4096, remat=False),
}


def build_cluster(io_executors: int = 8, device_bw: float = 2000.0):
    """One 'host' with a checkpoint filesystem device. The bandwidth number
    is the budget the scheduler constrains against (MB/s)."""
    dev = StorageDevice(name="ckpt-fs", bandwidth=device_bw,
                        per_stream_cap=device_bw / 4)
    return Cluster(workers=[WorkerNode(name="host0", cpus=4,
                                       io_executors=io_executors,
                                       storage=dev)])


def make_train_step(model: Model, opt: AdamWConfig):
    """The jitted step (params, opt_state, batch) -> (params, opt_state,
    loss, gnorm). Params and optimizer state are donated: the step writes
    the new ones into the old buffers, so one copy of the train state fits
    the device."""
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        new_p, new_o, gnorm = adamw_update(grads, params, opt_state, opt)
        return new_p, new_o, loss, gnorm
    return train_step


def train(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
          ckpt_dir: str | None, ckpt_every: int, io_aware: bool = True,
          resume: bool = True, log_path: str | None = None,
          opt: AdamWConfig | None = None, seed: int = 0):
    model = Model(cfg)
    opt = opt or AdamWConfig(total_steps=max(steps, 2), warmup_steps=max(steps // 10, 1))
    params, _ = model.init(jax.random.PRNGKey(seed))
    opt_state = adamw_init(params)
    corpus = SyntheticCorpus(cfg.vocab_size, seq, batch, seed=seed)

    train_step = make_train_step(model, opt)

    mgr = CheckpointManager(ckpt_dir, n_shards=8) if ckpt_dir else None
    start_step = 0
    if mgr and resume and mgr.latest_step() is not None:
        (params, opt_state), start_step = mgr.restore((params, opt_state))
        start_step += 1
        print(f"[train] resumed from step {start_step - 1}", flush=True)

    stop = {"now": False}

    def _sigterm(signum, frame):
        stop["now"] = True  # preemption: finish step, sync-save, exit
    old = signal.signal(signal.SIGTERM, _sigterm)

    log_f = open(log_path, "a") if log_path else None
    cluster = build_cluster()
    losses, step_s, save_stall_s = [], [], []
    t_start = time.monotonic()
    with IORuntime(cluster, backend=RealBackend()) as rt:
        loader = PrefetchLoader(corpus, depth=2) if io_aware else None
        for step in range(start_step, steps):
            b = loader.get(step) if io_aware else corpus.batch(step)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            t0 = time.perf_counter()
            params, opt_state, loss, gnorm = train_step(params, opt_state, b)
            jax.block_until_ready((params, opt_state, loss))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(loss))
            if log_f:
                log_f.write(json.dumps({"step": step, "loss": float(loss),
                                        "gnorm": float(gnorm),
                                        "t": time.monotonic() - t_start}) + "\n")
                log_f.flush()
            if mgr and ckpt_every and (step + 1) % ckpt_every == 0:
                t0 = time.perf_counter()
                mgr.save(step, (params, opt_state), sync=not io_aware)
                save_stall_s.append(time.perf_counter() - t0)
            if stop["now"]:
                print(f"[train] SIGTERM at step {step}: final sync save",
                      flush=True)
                if mgr:
                    mgr.save(step, (params, opt_state), sync=True)
                break
        if mgr:
            mgr.wait()
        stats = rt.stats()
    signal.signal(signal.SIGTERM, old)
    if log_f:
        log_f.close()
    return {"losses": losses, "steps_run": len(losses),
            "start_step": start_step, "step_s": step_s,
            "save_stall_s": save_stall_s,
            "final_loss": losses[-1] if losses else None,
            "runtime_stats": {k: v for k, v in stats.items()
                              if k not in ("tuners",)},
            "wall_s": time.monotonic() - t_start,
            "params": params, "opt_state": opt_state}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default=None)
    ap.add_argument("--preset", choices=list(PRESETS), default="20m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--no-io-aware", action="store_true")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--log", default=None)
    args = ap.parse_args(argv)
    configure_compile_cache()

    if args.arch:
        cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    else:
        cfg = PRESETS[args.preset]
    out = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                io_aware=not args.no_io_aware, resume=not args.no_resume,
                log_path=args.log)
    print(f"[train] {out['steps_run']} steps, final loss "
          f"{out['final_loss']:.4f}, wall {out['wall_s']:.1f}s")
    first, last = out["losses"][0], out["final_loss"]
    print(f"[train] loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
