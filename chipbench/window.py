"""One run of a training cell: set-up, the measured window, and the check of
what the timed path produced.

The window drives the program's training path in the order of the body of
``repro.launch.train.train``'s loop, with the program's own parts: the
prefetching loader under the I/O-aware runtime, the transfer to the device,
the jitted, donating train step, the wait for it, the loss read back, and,
where the mix asks for one, an asynchronous checkpoint save. Each call is a
host span.

Set-up makes the weights on the device from the seed, compiles (or loads
from the compile cache) the cell's own programs, and runs the mix's first
steps through the window's own step, loader and state. Those steps are what
the plain reference follows once the window has closed and the program's
state is freed. The window then goes on with the same state from the next
step, and ends at the first step boundary at or after ``seconds``.

Where the configuration states a ``layout`` (``spec.py``), all of the
program's side runs inside the program's mesh context over the cell's
chips: the weights are made straight into the program's shardings, AdamW's
state beside them, and each batch is split over the strategy's batch axes.
Without one, nothing of that is built and JAX places every array itself.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from . import check, model_under_test, traffic
from .checksum import device_checksums, host_checksums
from .clock import CompileClock, Spans
from .reference.common import seed_key
from .reference.follow import follow, leaf_norms, to_floats
from .spec import Cell, metric_reader
from .trace_reduce import reduce as reduce_trace

STEP_SPANS = ("loader_get", "to_device", "train_step", "block", "loss_read",
              "ckpt_save")


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, peaks: dict, make_step=None) -> dict:
    """The result of one run, as the line the benchmark prints.
    ``make_step`` replaces the program's ``make_train_step`` (tests break
    the timed path through it)."""
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    from repro.core import IORuntime, RealBackend
    from repro.data import PrefetchLoader, SyntheticCorpus
    from repro.launch.train import build_cluster, make_train_step
    from repro.models import Model
    from repro.optim import AdamWConfig, adamw_init

    conf, mix = cell.config, cell.mix
    ref = cell.reference
    m = ref.dims(conf)
    cfg = model_under_test.program_config(conf)
    opt = conf["train"]["optimizer"]
    B, S = conf["train"]["batch"], conf["train"]["seq"]
    dtype = getattr(jnp, conf["program"]["fields"]["dtype"])
    corpus_kw = {k: mix["corpus"][k] for k in ("structured", "noise")}
    n_follow = mix["follow_steps"]
    ckpt = mix.get("checkpoint")
    key = seed_key(seed)
    spans, clock = Spans(), CompileClock()
    devices = jax.devices()[:cell.chips]
    if "layout" in conf:
        place = model_under_test.Sharded(
            cfg, conf["layout"], model_under_test.layout_mesh(conf["layout"]),
            B)
        program = place.context()
        pin = lambda name: {"out_shardings": getattr(place, name)}
        to_device = lambda host_batch: jax.device_put(host_batch, place.batch)
    else:
        program = contextlib.nullcontext()
        pin = lambda name: {}
        to_device = lambda host_batch: {k: jnp.asarray(v)
                                        for k, v in host_batch.items()}

    with program:
        init = jax.jit(lambda k: ref.init_params(k, m, dtype), **pin("params"))
        params = init(key)
        model_under_test.check_layout(cfg, params)
        state = {"params": params,
                 "opt": jax.jit(adamw_init, **pin("opt"))(params)}
        del params
        step = (make_step or make_train_step)(Model(cfg), AdamWConfig(**opt))
        first_grad = jax.jit(lambda mom: leaf_norms(
            jax.tree.map(lambda a: a / (1 - opt["b1"]), mom)))
        change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, init(k))))
        checksum = jax.jit(device_checksums)
        corpus = SyntheticCorpus(cfg.vocab_size, S, B, seed=seed, **corpus_kw)

        prog = {"losses": [], "gnorms": []}
        numbers = {"batch_mismatch": 0}
        n_steps = n_nonfinite = 0
        save = None
        work = Path(tempfile.mkdtemp(prefix="chipbench_"))
        try:
            with IORuntime(build_cluster(), backend=RealBackend()) as rt:
                loader = PrefetchLoader(corpus, depth=mix["prefetch_depth"])

                def one_step(i):
                    with spans("loader_get"):
                        host_batch = loader.get(i)
                    with spans("to_device"):
                        batch = to_device(host_batch)
                    with spans("train_step"):
                        p, o, loss, gnorm = step(state["params"], state["opt"],
                                                 batch)
                    with spans("block"):
                        jax.block_until_ready((p, o, loss))
                    state["params"], state["opt"] = p, o
                    with spans("loss_read"):
                        return host_batch, float(loss), gnorm

                for i in range(n_follow):
                    host_batch, loss, gnorm = one_step(i)
                    want = traffic.batch(seed, i, B, S, cfg.vocab_size,
                                         **corpus_kw)
                    numbers["batch_mismatch"] += sum(
                        not np.array_equal(host_batch[k], want[k])
                        for k in want)
                    prog["losses"].append(loss)
                    prog["gnorms"].append(float(gnorm))
                    if i == 0:
                        prog["grad_norms"] = to_floats(
                            first_grad(state["opt"].m))
                prog["change_norms"] = to_floats(change(state["params"], key))
                if ckpt:
                    mgr = CheckpointManager(work / "ckpt",
                                            n_shards=ckpt["n_shards"])
                    jax.block_until_ready(
                        checksum((state["params"], state["opt"])))
                setup_s = time.monotonic() - t_start

                if trace:
                    jax.profiler.start_trace(str(work / "trace"))
                spans.seconds.clear()
                compiles_before = clock.compiles
                i = n_follow
                with spans("window"):
                    t0 = time.perf_counter()
                    while True:
                        _, loss, _ = one_step(i)
                        i += 1
                        n_steps += 1
                        n_nonfinite += not math.isfinite(loss)
                        if ckpt and save is None \
                                and time.perf_counter() - t0 >= ckpt["at_s"]:
                            tree = (state["params"], state["opt"])
                            save = {"step": i - 1, "sums": checksum(tree),
                                    "call_ns": time.time_ns(),
                                    "call_rt": rt.backend.now()}
                            with spans("ckpt_save"):
                                save["started"] = mgr.save(i - 1, tree)
                            del tree
                        if time.perf_counter() - t0 >= seconds:
                            break
                    window_s = time.perf_counter() - t0
                if trace:
                    jax.profiler.stop_trace()
                compiles = clock.compiles - compiles_before
                if save:
                    mgr.wait()
                    save["samples"] = [
                        s for d in rt.backend.telemetry.devices.values()
                        for s in d.samples
                        if s[0] >= save["call_rt"] and s[1] > 0]
            memory_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                            for d in devices]
            like = jax.eval_shape(lambda: (state["params"], state["opt"]))
            state.clear()
            del loader
            gc.collect()

            if ckpt:
                numbers.update(_check_save(save, mgr, work / "ckpt", like))
            traced = reduce_trace(next((work / "trace").rglob("*.xplane.pb")),
                                  STEP_SPANS) if trace else None
        finally:
            shutil.rmtree(work, ignore_errors=True)

    batches = [traffic.batch(seed, i, B, S, cfg.vocab_size, **corpus_kw)
               for i in range(n_follow)]
    followed = follow(ref, m, opt, key,
                      [(jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]))
                       for b in batches],
                      compute_dtype=jnp.float32, param_dtype=jnp.float32,
                      row_block=conf["reference"]["row_block"],
                      devices=devices if "layout" in conf else None)
    numbers.update(check.training_gaps(prog, followed))
    correct, rows = check.verdict(numbers, cell.limits)

    failed_saves = int(bool(ckpt) and any(
        numbers[k] for k in ("ckpt_saves_missing", "ckpt_bad_shards",
                             "ckpt_roundtrip_leaves")))
    tokens = n_steps * B * S
    observed = {"setup_s": setup_s, "train_tokens_per_s": tokens / window_s}
    if save and save.get("commit_s") is not None:
        observed["ckpt_commit_s"] = save["commit_s"]
    ctx = {"window_s": window_s, "steps": n_steps, "tokens": tokens,
           "spans": dict(spans.seconds), "save": save, "trace": traced,
           "flops_per_step": cell.flops.train_step_flops(m, B, S),
           "peak": peaks, "chips": cell.chips}
    if trace:
        wanted = cell.per_layer
        values = {mt["name"]: metric_reader(mt["name"])(ctx) for mt in wanted}
    else:
        wanted = cell.end_to_end
        values = {mt["name"]: observed.get(mt["name"]) for mt in wanted}
    metrics = {mt["name"]: {"value": values[mt["name"]], "unit": mt["unit"]}
               for mt in wanted if values[mt["name"]] is not None}

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": None if None in memory_peaks
           else max(memory_peaks),
           "memory_peak_bytes_per_chip": memory_peaks}
    result = {"correct": correct,
              "attempted": n_steps + (1 if ckpt else 0),
              "failed": n_nonfinite + failed_saves,
              "metrics": metrics, "device": dev}
    if traced:
        dev["busy_s"] = traced["busy_s"]
        dev["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["window"] = {"steps": n_steps, "seconds": window_s,
                        "compiles": compiles, "first_losses": prog["losses"],
                        "reference_losses": followed["losses"]}
    result["checks"] = {name: [value, limit] for name, value, limit in rows}
    return result


def _check_save(save, mgr, directory: Path, like) -> dict:
    """What the save committed, read back: the manifest in place, every
    shard at its declared size, every leaf equal to what was saved. Sets
    ``commit_s`` on ``save``: from the call to the manifest's write."""
    out = {"ckpt_saves_missing": 1, "ckpt_bad_shards": 0,
           "ckpt_roundtrip_leaves": 0}
    if not save or not save["started"]:
        return out
    step_dir = directory / f"step_{save['step']:08d}"
    manifest_path = step_dir / "MANIFEST.json"
    if not manifest_path.exists():
        return out
    out["ckpt_saves_missing"] = 0
    save["commit_s"] = (manifest_path.stat().st_mtime_ns - save["call_ns"]) / 1e9
    manifest = json.loads(manifest_path.read_text())
    for frag in manifest["shards"]:
        path = step_dir / frag["file"]
        out["ckpt_bad_shards"] += int(
            not path.exists() or path.stat().st_size != frag["total_bytes"])
    if out["ckpt_bad_shards"]:
        out["ckpt_roundtrip_leaves"] = len(save["sums"])
        return out
    restored, _ = mgr.restore(like, step=save["step"])
    saved = [np.asarray(s) for s in save["sums"]]
    read = host_checksums(restored)
    out["ckpt_roundtrip_leaves"] = sum(
        not np.array_equal(a, b) for a, b in zip(saved, read))
    return out
