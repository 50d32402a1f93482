import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb driver: compile named variants of the three chosen cells
and record their roofline terms to artifacts/perf/<cell>__<variant>.json.

    PYTHONPATH=src python -m benchmarks.hillclimb --cell commandr --variant mb2
    PYTHONPATH=src python -m benchmarks.hillclimb --all
"""
import argparse
import json
import time

VARIANTS = {
    # (arch, shape, tuning)
    "commandr": {
        "arch": "command-r-35b", "shape": "train_4k",
        "variants": {
            "baseline": {},
            "mb2": {"microbatches": 2},
            "mb2_zero1": {"microbatches": 2, "zero1": True},
            "mb4_zero1": {"zero1": True},
            "sp": {"config": {"seq_parallel": True}},
            "mb2_sp": {"microbatches": 2,
                       "config": {"seq_parallel": True}},
            "mb2_gcast": {"microbatches": 2,
                          "config": {"grad_cast": True}},
        },
    },
    "moonshot": {
        "arch": "moonshot-v1-16b-a3b", "shape": "train_4k",
        "variants": {
            "baseline": {},
            "grouped16": {"config": {"moe_groups": 16}},
            "grouped16_cf1": {"config": {"moe_groups": 16,
                                         "capacity_factor": 1.0}},
            "grouped16_zero1": {"config": {"moe_groups": 16}, "zero1": True},
            "sp": {"config": {"seq_parallel": True}},
            "gcast": {"config": {"grad_cast": True}},
        },
    },
    "meshgraphnet": {
        "arch": "meshgraphnet", "shape": "ogb_products",
        "variants": {
            "baseline": {},
            "part_h086": {"mode": "partitioned", "halo_frac": 0.86},
            "part_h045": {"mode": "partitioned", "halo_frac": 0.45},
            "part_h025": {"mode": "partitioned", "halo_frac": 0.25},
        },
    },
}


def run_variant(cell_name: str, variant: str, out_dir="artifacts/perf"):
    import jax

    from repro.configs import get_arch
    from repro.launch.hlo_cost import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell

    spec = VARIANTS[cell_name]
    tuning = spec["variants"][variant]
    arch = get_arch(spec["arch"])
    mesh = make_production_mesh(multi_pod=False)
    rec = {"cell": cell_name, "arch": spec["arch"], "shape": spec["shape"],
           "variant": variant, "tuning": tuning, "status": "ok"}
    t0 = time.perf_counter()
    try:
        cell = build_cell(arch, spec["shape"], mesh, tuning=dict(tuning))
        with jax.set_mesh(mesh):
            compiled = jax.jit(
                cell.step_fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
                donate_argnums=cell.donate,
            ).lower(*cell.args).compile()
        ma = compiled.memory_analysis()
        rec["peak_gib"] = float(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30)
        cost = analyze_hlo(compiled.as_text())
        rec["cost"] = cost
        rec["meta"] = cell.meta
        # roofline terms
        PEAK, HBM, LINK = 197e12, 819e9, 50e9
        rec["compute_s"] = cost["flops"] / PEAK
        rec["memory_s"] = cost["bytes"] / HBM
        rec["collective_s"] = cost["collective_bytes"] / LINK
        rec["step_s"] = max(rec["compute_s"], rec["memory_s"],
                            rec["collective_s"])
        rec["bottleneck"] = max(
            ("compute", "memory", "collective"),
            key=lambda k: rec[f"{k}_s"])
        chips = 256
        rec["roofline_frac"] = (
            cell.meta["model_flops"] / chips / PEAK / rec["step_s"])
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        import traceback
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["wall_s"] = time.perf_counter() - t0
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell_name}__{variant}.json"),
              "w") as f:
        json.dump(rec, f, indent=1, default=str)
    if rec["status"] == "ok":
        print(f"[ok] {cell_name}/{variant}: step {rec['step_s']:.2f}s "
              f"(C {rec['compute_s']:.2f} M {rec['memory_s']:.2f} "
              f"X {rec['collective_s']:.2f}) bneck={rec['bottleneck']} "
              f"frac={rec['roofline_frac']:.2%} peak={rec['peak_gib']:.1f}GiB",
              flush=True)
    else:
        print(f"[error] {cell_name}/{variant}: {rec['error'][:200]}",
              flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None)
    ap.add_argument("--variant", default=None)
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    if args.all:
        for cell_name, spec in VARIANTS.items():
            for variant in spec["variants"]:
                run_variant(cell_name, variant)
    else:
        run_variant(args.cell, args.variant or "baseline")


if __name__ == "__main__":
    main()
