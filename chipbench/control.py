"""Readings a cell's limits are set from, on the chip, at the cell's size.

    python chipbench/control.py --workload <cell> --seeds 12 --control-seeds 3

In one process: sound runs of the program on ``--seeds`` seeds (the window
cut to ``--seconds``, one by default, since the first steps are what is
compared; a checkpoint cell needs its save's time and the commit), then on
``--control-seeds`` seeds the control and a planted fault, each put in the
program's place and compared with the float32 reference as a run compares
the program:

- control: the reference in bfloat16, parameters stored in bfloat16 and
  activations computed in it, the step a later change might be tempted by;
- half batch: the reference on the first half of each batch's rows, the
  mean taken over them.

A step that returns its state unchanged reads 1 on ``change_gap`` by the
measure itself and needs no run. Where the configuration states a layout,
every reference here is spread over the cell's chips, as in its runs. The
benchmark's own runs never run this.
Prints one JSON line of readings; ``--out`` also writes it to a file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))

    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    from chipbench import check, traffic, window
    from chipbench.reference.common import seed_key
    from chipbench.reference.follow import follow
    from chipbench.spec import HERE, load_cell, load_json
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()

    cell = load_cell(args.workload)
    peaks = load_json(HERE / "peaks.json")["devices"][
        jax.devices()[0].device_kind]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    program = []
    for seed in seeds:
        out = window.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         t_start=time.monotonic(), peaks=peaks)
        program.append({"seed": seed, "correct": out["correct"],
                        **{k: v for k, (v, _) in out["checks"].items()}})
        print(json.dumps(program[-1]), file=sys.stderr, flush=True)

    conf, mix = cell.config, cell.mix
    ref = cell.reference
    m = ref.dims(conf)
    opt = conf["train"]["optimizer"]
    B, S = conf["train"]["batch"], conf["train"]["seq"]
    rb = conf["reference"]["row_block"]
    devices = jax.devices()[:cell.chips] if "layout" in conf else None
    corpus_kw = {k: mix["corpus"][k] for k in ("structured", "noise")}
    control, half = [], []
    for seed in seeds[:args.control_seeds]:
        key = seed_key(seed)
        rows = [traffic.batch(seed, i, B, S, m["vocab_size"], **corpus_kw)
                for i in range(mix["follow_steps"])]
        batches = [(jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]))
                   for b in rows]
        f32 = dict(compute_dtype=jnp.float32, param_dtype=jnp.float32)
        full = follow(ref, m, opt, key, batches, row_block=rb,
                      devices=devices, **f32)
        low = follow(ref, m, opt, key, batches, row_block=rb,
                     compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                     precision="default", devices=devices)
        part = follow(ref, m, opt, key,
                      [(t[:B // 2], y[:B // 2]) for t, y in batches],
                      row_block=rb, devices=devices, **f32)
        control.append({"seed": seed, **check.training_gaps(low, full)})
        half.append({"seed": seed, **check.training_gaps(part, full)})
        print(json.dumps({"control": control[-1], "half_batch": half[-1]}),
              file=sys.stderr, flush=True)

    result = {"workload": cell.name, "program": program, "control": control,
              "half_batch": half,
              "seconds": time.monotonic() - T_START}
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
