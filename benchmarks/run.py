"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run [--only X]``.

Sections map 1:1 onto the paper's tables/figures (+ the TPU-side roofline
artifacts). Each renders as an aligned text table. Kernel sections are
additionally written to ``BENCH_kernels.json``, the serving section to
``BENCH_serving.json``, the vision section to ``BENCH_cnn.json`` and the
fault sections to ``BENCH_faults.json`` at the repo root so future PRs can
track the perf trajectory (cached-weight vs per-call serving, fused-conv
vs im2col, backend sweep, engine hot-loop tokens/sec + TTFT,
accuracy-vs-BER mitigation frontier). The MoE sections (packed expert
banks vs float einsum, expert-parallel/pipelined engine scaling) also land
in ``BENCH_serving.json`` under ``moe_layer``/``moe_device_scaling``.
``--smoke`` shrinks the serving and fault benchmarks to CI scale without
changing the artifact shape.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time


def render(title: str, rows: list) -> None:
    print(f"\n== {title} " + "=" * max(1, 70 - len(title)))
    if not rows:
        print("  (no rows — run the producing step first)")
        return
    cols = list(rows[0].keys())
    widths = {c: max(len(str(c)), *(len(str(r.get(c, ""))) for r in rows))
              for c in cols}
    print("  " + "  ".join(str(c).ljust(widths[c]) for c in cols))
    for r in rows:
        print("  " + "  ".join(str(r.get(c, "")).ljust(widths[c]) for c in cols))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on section names")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale serving benchmark (same artifact shape)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (autotune_bench, cnn_bench, fault_bench, kernel_bench,
                   lm_roofline, moe_bench, paper_figures, serve_bench)

    serve_throughput = functools.partial(serve_bench.serve_throughput,
                                         smoke=args.smoke)
    serve_scaling = functools.partial(serve_bench.serve_device_scaling,
                                      smoke=args.smoke)
    moe_layer = functools.partial(moe_bench.moe_layer_comparison,
                                  smoke=args.smoke)
    moe_scaling = functools.partial(moe_bench.moe_device_scaling,
                                    smoke=args.smoke)
    serve_gateway = functools.partial(serve_bench.gateway_bench,
                                      smoke=args.smoke)
    cnn_throughput = functools.partial(cnn_bench.cnn_throughput,
                                       smoke=args.smoke)
    cnn_crosscheck = functools.partial(cnn_bench.cnn_sim_crosscheck,
                                       smoke=args.smoke)
    fault_frontier = functools.partial(fault_bench.fault_frontier,
                                       smoke=args.smoke)
    autotune_regret = functools.partial(autotune_bench.autotune_regret,
                                        smoke=args.smoke)
    sections = [
        ("fig13a: capacity sweep", paper_figures.fig13a_capacity_sweep),
        ("fig13b: bandwidth sweep", paper_figures.fig13b_bandwidth_sweep),
        ("fig14: energy efficiency vs counterparts", paper_figures.fig14_energy_efficiency),
        ("fig15: per-area speedup vs counterparts", paper_figures.fig15_speedup),
        ("table3: accelerator comparison", paper_figures.table3_comparison),
        ("fig16: latency/energy breakdown (resnet50)", paper_figures.fig16_breakdown),
        ("fig17: add-on area breakdown", paper_figures.fig17_area_overhead),
        ("paper-claims check (§5.3)", paper_figures.paper_claims_check),
        ("kernel: Eq.1 backend comparison (CPU)", kernel_bench.backend_comparison),
        ("kernel: cached PackedWeight vs per-call quantize+pack",
         kernel_bench.serving_path_comparison),
        ("kernel: fused implicit-im2col conv vs materialized",
         kernel_bench.fused_conv_comparison),
        ("kernel: BlockSpec tile plans (TPU target)", kernel_bench.tile_plan_sweep),
        # "autotune:" (not "kernel:") so `--only kernel` stays the quick
        # kernel sweep and `--only autotune` selects the regret bench.
        ("autotune: picked-vs-best regret (cost model vs exhaustive)",
         autotune_regret),
        ("roofline: single-pod 16x16 (from dry-run)", lm_roofline.roofline_table),
        ("dry-run: multi-pod 2x16x16 compile status", lm_roofline.multipod_check),
        ("perf: baseline vs optimized step-time bound", lm_roofline.baseline_vs_optimized),
        ("serve: engine throughput (legacy vs fused hot loop)", serve_throughput),
        ("serve: device-count scaling (chips=data x banks=model mesh)",
         serve_scaling),
        ("serve: MoE expert FFN packed vs float einsum (per-layer)",
         moe_layer),
        ("serve: MoE engine scaling (experts=chips / pipeline stages)",
         moe_scaling),
        ("serve: overload gateway (Poisson mixed LM+vision load-gen)",
         serve_gateway),
        ("cnn: vision engine throughput (batch x precision x model)",
         cnn_throughput),
        ("cnn: measured vs simulated fps (pim.calibrate cross-check)",
         cnn_crosscheck),
        ("faults: accuracy-vs-BER frontier (ECC on/off)", fault_frontier),
        ("faults: mitigation overhead (redundancy x, die area)",
         fault_bench.fault_overhead),
    ]
    # Kernel sections feeding BENCH_kernels.json (rows reused, not re-run).
    json_keys = {
        kernel_bench.serving_path_comparison: "serving_cached_vs_percall",
        kernel_bench.fused_conv_comparison: "fused_conv_vs_im2col",
        kernel_bench.backend_comparison: "backend_comparison",
        kernel_bench.tile_plan_sweep: "tile_plans",
        autotune_regret: "autotune_regret",
    }
    payload = {}
    serve_payload = {}
    cnn_payload = {}
    fault_payload = {}
    t0 = time.time()
    failures = []
    for title, fn in sections:
        if args.only and args.only not in title:
            continue
        try:
            rows = fn()
            render(title, rows)
            if fn in json_keys:
                payload[json_keys[fn]] = rows
            elif fn is serve_throughput:
                serve_payload["serve_throughput"] = rows
            elif fn is serve_scaling:
                serve_payload["device_scaling"] = rows
            elif fn is moe_layer:
                serve_payload["moe_layer"] = rows
            elif fn is moe_scaling:
                serve_payload["moe_device_scaling"] = rows
            elif fn is serve_gateway:
                serve_payload["gateway"] = rows
            elif fn is cnn_throughput:
                cnn_payload["throughput"] = rows
            elif fn is cnn_crosscheck:
                cnn_payload["sim_crosscheck"] = rows
            elif fn is fault_frontier:
                fault_payload["frontier"] = rows
            elif fn is fault_bench.fault_overhead:
                fault_payload["overhead"] = rows
            if serve_payload:
                serve_payload["smoke"] = args.smoke
            if cnn_payload:
                cnn_payload["smoke"] = args.smoke
            if fault_payload:
                fault_payload["smoke"] = args.smoke
        except Exception as e:  # keep the suite running; report at the end
            failures.append((title, repr(e)))
            print(f"\n== {title} FAILED: {e!r}")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for data, name in ((payload, "BENCH_kernels.json"),
                       (serve_payload, "BENCH_serving.json"),
                       (cnn_payload, "BENCH_cnn.json"),
                       (fault_payload, "BENCH_faults.json")):
        if not data:
            continue
        path = os.path.join(repo_root, name)
        try:
            # Merge over the committed artifact so a filtered run (--only
            # matching one section) or a section failure updates its own
            # keys without destroying the rows other sections produced.
            old = {}
            if os.path.exists(path):
                with open(path) as fh:
                    old = json.load(fh)
                data = {**old, **data}
            if name == "BENCH_serving.json" and old.get("device_scaling") \
                    and not data.get("device_scaling"):
                # Loud failure, never a silent skip: losing the committed
                # device-scaling rows means a section-wiring bug upstream
                # (the merge above is what preserves them on filtered runs).
                raise RuntimeError(
                    "refusing to rewrite BENCH_serving.json: it would drop "
                    "the committed device_scaling rows (section produced "
                    f"{data.get('device_scaling')!r})")
            with open(path, "w") as fh:
                json.dump(data, fh, indent=1)
            print(f"\nwrote {path}")
        except Exception as e:
            failures.append((name, repr(e)))

    print(f"\nbenchmarks done in {time.time() - t0:.1f}s")
    if failures:
        for t, e in failures:
            print("FAILED:", t, e)
        sys.exit(1)


if __name__ == "__main__":
    main()
