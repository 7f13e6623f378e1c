"""Run one cell of the benchmark once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from BENCHMARK.json. The run needs the chips the cell asks for: it
exits non-zero, printing no result, when JAX finds no TPU, fewer chips, or
a chip whose kind the peaks table lacks. The last line of standard output
is one JSON object; the numbers ``correct`` was decided by close standard
error and the line itself, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))

    from chipbench.spec import HERE, load_cell, load_json
    cell = load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    peaks = load_json(HERE / "peaks.json")["devices"]
    if devices[0].device_kind not in peaks:
        print(f"chipbench: no peaks for device kind "
              f"{devices[0].device_kind!r}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    # every program of set-up, the small ones too, is read back from the
    # cache after a cell's first run, so set-up does the same work each run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import window
    result = window.run(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START,
                        peaks=peaks[devices[0].device_kind])
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
