import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) combination:
  jax.jit(step).lower(**ShapeDtypeStructs).compile()
must succeed on the 16x16 single-pod mesh AND the 2x16x16 two-pod mesh.
Per cell we record memory_analysis, cost_analysis, and the collective
traffic parsed from the partitioned HLO — the roofline reads these JSONs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--force]
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from ..configs import ARCHS, SHAPES, cell_supported, get_config
from ..distributed import mesh_context
from ..launch.mesh import make_production_mesh
from ..launch.specs import build_cell

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
_WHILE_RE = re.compile(r"while\(.*?\), condition=%([\w.\-]+), body=%([\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_CALL_RE = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")


def _split_computations(hlo_text: str):
    comps: dict[str, list[str]] = {}
    cur, entry = None, None
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _COMP_RE.match(s)
        if m and s.endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            if s.startswith("ENTRY") or line.startswith("ENTRY"):
                entry = cur
        elif cur is not None:
            comps[cur].append(s)
    return comps, entry


def _trip_count(cond_lines: list[str]) -> int:
    consts = [int(m.group(1)) for l in cond_lines
              for m in _CONST_RE.finditer(l)]
    return max(consts) if consts else 1


def parse_collectives(hlo_text: str) -> dict:
    """Trip-count-aware per-device collective traffic (DESIGN.md §8).

    Collectives inside while bodies (lax.scan over layers / chunks) appear
    once in the HLO text; we multiply by the loop trip count parsed from the
    cond region's s32 constant. Traffic model: bytes = result_size * factor;
    factor: all-reduce 2, reduce-scatter g, others 1 (ring models — the
    all-gather result already includes the group factor)."""
    comps, entry = _split_computations(hlo_text)
    # nesting: computation -> [(child_body, trip)], from while ops inside it
    children: dict[str, list[tuple[str, int]]] = {}
    for name, lines in comps.items():
        for l in lines:
            w = _WHILE_RE.search(l)
            if w:
                cond, body = w.groups()
                trip = _trip_count(comps.get(cond, []))
                children.setdefault(name, []).append((body, trip))
    # multipliers via BFS from entry
    mult: dict[str, float] = {}
    stack = [(entry, 1.0)] if entry else []
    while stack:
        name, m = stack.pop()
        if name in mult and mult[name] >= m:
            continue
        mult[name] = m
        for body, trip in children.get(name, []):
            stack.append((body, m * trip))
    # computations never reached from the entry via while (fusions etc.)
    # inherit 1x; the collectives we care about sit directly in region bodies
    per_op: dict[str, float] = {}
    count: dict[str, int] = {}
    per_op_static: dict[str, float] = {}
    for name, lines in comps.items():
        m = mult.get(name, 1.0)
        for l in lines:
            cm = _COLL_RE.search(l)
            if not cm:
                continue
            dtype, dims, op = cm.groups()
            nbytes = _DTYPE_BYTES.get(dtype)
            if nbytes is None:
                continue
            size = nbytes
            for d in dims.split(","):
                if d:
                    size *= int(d)
            g = 1
            gm = _GROUPS_RE.search(l)
            if gm:
                g = int(gm.group(2))
            else:
                gl = _GROUPS_LIST_RE.search(l)
                if gl:
                    g = len(gl.group(1).split(","))
            factor = {"all-reduce": 2.0,
                      "reduce-scatter": float(max(g, 1))}.get(op, 1.0)
            per_op[op] = per_op.get(op, 0.0) + size * factor * m
            per_op_static[op] = per_op_static.get(op, 0.0) + size * factor
            count[op] = count.get(op, 0) + 1
    return {"bytes_by_op": per_op, "count_by_op": count,
            "bytes_by_op_body_once": per_op_static,
            "total_bytes": sum(per_op.values())}


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = repr(ma)
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, force: bool = False,
             tag: str = "", cfg_override=None, strategy: str = "tp_fsdp") -> dict:
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape}__{mesh_kind}{tag}"
    path = ARTIFACTS / f"{name}.json"
    if path.exists() and not force:
        cached = json.loads(path.read_text())
        if cached.get("status") != "error":
            return cached  # errors are retried (they are bugs being fixed)

    cfg = cfg_override or get_config(arch)
    cell = SHAPES[shape]
    ok, reason = cell_supported(cfg, cell)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "tag": tag,
           "params": cfg.param_count(), "active_params": cfg.active_param_count()}
    if not ok:
        rec.update(status="skipped", reason=reason)
        path.write_text(json.dumps(rec, indent=1))
        return rec

    from ..distributed.sharding import STRATEGIES
    rec["strategy"] = strategy
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        with mesh_context(mesh, rules=STRATEGIES[strategy]):
            if cell.kind == "decode" and \
                    cell.global_batch % (mesh.devices.size // mesh.shape["model"]):
                cfg = cfg.replace(decode_batch_replicated=True)
            from ..distributed.sharding import OPT_RULES
            fn, args, out_sh = build_cell(cfg, cell, mesh,
                                          opt_rules=OPT_RULES.get(strategy))
            jitted = jax.jit(fn, out_shardings=out_sh) if out_sh else jax.jit(fn)
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            cost = compiled.cost_analysis() or {}
            cost = {k: float(v) for k, v in cost.items()
                    if isinstance(v, (int, float))
                    and "utilization" not in k}
            hlo = compiled.as_text()
            coll = parse_collectives(hlo)
            rec.update(
                status="ok",
                lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
                n_devices=int(mesh.devices.size),
                cost_analysis={k: cost[k] for k in sorted(cost)[:40]},
                flops=float(cost.get("flops", 0.0)),
                bytes_accessed=float(cost.get("bytes accessed", 0.0)),
                collectives=coll,
                memory=memory_summary(compiled),
                hlo_bytes=len(hlo),
            )
    except Exception as e:  # record failures: they are bugs to fix
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--strategy", default="tp_fsdp")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.list:
        for a in archs:
            for s in shapes:
                ok, why = cell_supported(get_config(a), SHAPES[s])
                print(f"{a:24} {s:12} {'RUN' if ok else 'SKIP: ' + why}")
        return 0

    failures = 0
    for a in archs:
        for s in shapes:
            for m in meshes:
                rec = run_cell(a, s, m, force=args.force,
                               strategy=args.strategy, tag=args.tag)
                line = f"{a:24} {s:12} {m:6} {rec['status']:8}"
                if rec["status"] == "ok":
                    line += (f" compile={rec['compile_s']:7.1f}s "
                             f"flops={rec['flops']:.3e} "
                             f"coll={rec['collectives']['total_bytes']:.3e}B")
                elif rec["status"] == "error":
                    line += " " + rec["error"][:120]
                    failures += 1
                else:
                    line += " " + rec.get("reason", "")
                print(line, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
