import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the XLA_FLAGS assignment above MUST precede any jax import (jax
# locks the device count on first init), which is why it sits before the
# module docstring and all other imports.
DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this records, to artifacts/dryrun/<mesh>/<arch>__<shape>.json:
  * memory_analysis  — per-device argument/output/temp/alias bytes
  * cost_analysis    — per-device HLO flops and bytes accessed
  * collective bytes — parsed from the compiled HLO text, summed per op kind
  * meta             — model_flops, param counts, step kind

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both            # all
  PYTHONPATH=src python -m repro.launch.dryrun --arch fm --shape all
"""

import argparse
import json
import re
import time
import traceback

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of all dtype[shape] terms in an HLO result type."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-kind {count, bytes} summed over collective ops in compiled HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"^(?:ROOT )?%?[\w.\-]+ = (.*)$", ls)
        if not m:
            continue
        rest = m.group(1)
        opm = re.match(r"^((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*)) "
                       r"([a-z0-9\-]+)", rest)
        if not opm:
            continue
        result_type, op = opm.groups()
        # strip -start/-done suffixes (async collectives appear twice;
        # count only the -start or the plain form)
        base = op.replace("-start", "")
        if op.endswith("-done"):
            continue
        if base in _COLLECTIVES:
            out[base]["count"] += 1
            out[base]["bytes"] += _shape_bytes(result_type)
    out["total_bytes"] = sum(
        v["bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_count"] = sum(
        v["count"] for k, v in out.items() if isinstance(v, dict))
    return out


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             out_dir: str) -> dict:
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import SkippedCell, build_cell

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    arch = get_arch(arch_id)
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "status": "ok"}
    t0 = time.perf_counter()
    try:
        cell = build_cell(arch, shape_name, make_production_mesh(
            multi_pod=multi_pod))
    except SkippedCell as e:
        rec["status"] = "skipped"
        rec["reason"] = str(e)
        _write(out_dir, mesh_name, arch_id, shape_name, rec)
        return rec
    try:
        from repro.launch.hlo_cost import analyze_hlo

        mesh = make_production_mesh(multi_pod=multi_pod)
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate,
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*cell.args)
            rec["lower_s"] = time.perf_counter() - t0
            t1 = time.perf_counter()
            compiled = lowered.compile()
        rec["compile_s"] = time.perf_counter() - t1
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_estimate_bytes": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        }
        # raw XLA numbers (loop bodies counted ONCE — kept for reference)
        ca = compiled.cost_analysis() or {}
        rec["cost_raw"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
        }
        # loop-corrected cost model (launch/hlo_cost.py): trip counts
        # multiplied, HBM bytes counted at fusion boundaries
        hlo_text = compiled.as_text()
        rec["cost"] = analyze_hlo(hlo_text)
        # flat op census (each collective op once, no trip scaling) — the
        # loop-corrected totals live in rec["cost"]["collectives"]
        rec["collectives_flat"] = parse_collectives(hlo_text)
        rec["collectives"] = {
            "total_bytes": rec["cost"]["collective_bytes"],
            "by_kind": rec["cost"]["collectives"],
        }
        rec["meta"] = cell.meta
    except Exception as e:  # a failed cell is a bug — record it loudly
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = time.perf_counter() - t0
    _write(out_dir, mesh_name, arch_id, shape_name, rec)
    return rec


def _write(out_dir, mesh_name, arch_id, shape_name, rec):
    d = os.path.join(out_dir, mesh_name)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{arch_id}__{shape_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    from repro.configs import ARCH_IDS, get_arch

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for multi in meshes:
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        for arch_id in archs:
            arch = get_arch(arch_id)
            shapes = (list(arch.shapes) if args.shape == "all"
                      else [args.shape])
            for shape_name in shapes:
                path = os.path.join(args.out, mesh_name,
                                    f"{arch_id}__{shape_name}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        old = json.load(f)
                    if old.get("status") in ("ok", "skipped"):
                        print(f"[skip-existing] {mesh_name} {arch_id} "
                              f"{shape_name}")
                        continue
                rec = run_cell(arch_id, shape_name, multi, args.out)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    gb = rec["memory"]["peak_estimate_bytes"] / 2**30
                    extra = (f"compile {rec['compile_s']:.1f}s "
                             f"peak/dev {gb:.2f} GiB "
                             f"flops/dev {rec['cost']['flops']:.3e} "
                             f"coll {rec['collectives']['total_bytes']:.3e}B")
                elif status == "error":
                    failures += 1
                    extra = rec["error"][:200]
                else:
                    extra = rec.get("reason", "")
                print(f"[{status}] {mesh_name} {arch_id} {shape_name} {extra}",
                      flush=True)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
