"""Benchmark aggregator: one section per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized subset
    PYTHONPATH=src python -m benchmarks.run --only gk_step --emit-json
                                                       # BENCH_pr3.json

``--emit-json [PATH]`` writes every section's machine-readable records to
one standardized json (default name ``BENCH_pr3.json``) so future PRs can
diff their speedups against a stored baseline:

    {"schema": "repro-bench/v1", "quick": bool, "backend": str,
     "sections": {<name>: <section dict, e.g. schema gk_step/v1>}}

``benchmarks.reanalyze`` validates/re-derives the file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / fewer steps (CI)")
    ap.add_argument("--only", default=None,
                    choices=["table1", "table2", "fig1", "fig2", "roofline",
                             "kernels", "sparse", "gk_step", "dist",
                             "session", "serve", "update", "chaos",
                             "sketch", "sketchres"])
    ap.add_argument("--emit-json", nargs="?", const="BENCH_pr3.json",
                    default=None, metavar="PATH",
                    help="write section records to a standardized BENCH "
                         "json (default PATH: BENCH_pr3.json; use --only "
                         "dist --emit-json BENCH_pr4.json for the device-"
                         "scaling artifact, --only session --emit-json "
                         "BENCH_pr5.json for the tracked-session one, "
                         "--only serve --emit-json BENCH_pr6.json for the "
                         "serve-traffic one, --only update --emit-json "
                         "BENCH_pr7.json for the rank-k-update one, "
                         "--only chaos --emit-json BENCH_pr8.json for the "
                         "fault-injection one, --only sketch --emit-json "
                         "BENCH_pr9.json for the sketch-solver frontier, "
                         "--only sketchres --emit-json BENCH_pr10.json "
                         "for the sketch-resident entry-drift one)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (chaos_bench, dist_bench, fig1, fig2,
                            gk_step_bench, kernels_bench, roofline,
                            serve_bench, session_bench, sketch_bench,
                            sketchres_bench, sparse_bench, table1, table2,
                            update_bench)

    t0 = time.time()
    sections = []
    if args.only in (None, "table1"):
        sizes = table1.SIZES[:4] if args.quick else table1.SIZES
        sections.append(("table1", lambda: table1.run(sizes=sizes,
                                                      repeats=1 if args.quick
                                                      else 3)))
    if args.only in (None, "table2"):
        sizes2 = table2.SIZES[:2] if args.quick else table2.SIZES
        sections.append(("table2", lambda: table2.run(sizes=sizes2)))
    if args.only in (None, "fig1"):
        sections.append(("fig1", fig1.run))
    if args.only in (None, "fig2"):
        sections.append(("fig2", lambda: fig2.run(steps=40 if args.quick
                                                  else fig2.STEPS)))
    if args.only in (None, "kernels"):
        sections.append(("kernels", kernels_bench.run))
    if args.only in (None, "sparse"):
        sections.append(("sparse", lambda: sparse_bench.run(
            sizes=sparse_bench.SIZES[:1] if args.quick else None,
            repeats=1 if args.quick else 3)))
    if args.only in (None, "gk_step"):
        sections.append(("gk_step", lambda: gk_step_bench.run(
            sizes=gk_step_bench.QUICK_SIZES if args.quick else None,
            repeats=1 if args.quick else 3)))
    if args.only in (None, "dist"):
        sections.append(("dist", lambda: dist_bench.run(
            quick=args.quick,
            repeats=1 if args.quick else 3)))
    if args.only in (None, "session"):
        sections.append(("session", lambda: session_bench.run(
            sizes=session_bench.QUICK_SIZES if args.quick else None,
            repeats=1 if args.quick else 3,
            steps=4 if args.quick else session_bench.STEPS)))
    if args.only in (None, "update"):
        sections.append(("update", lambda: update_bench.run(
            sizes=update_bench.QUICK_SIZES if args.quick else None,
            repeats=1 if args.quick else 3,
            steps=4 if args.quick else update_bench.STEPS)))
    if args.only in (None, "chaos"):
        sections.append(("chaos", lambda: chaos_bench.run(
            requests=chaos_bench.QUICK_REQUESTS if args.quick
            else chaos_bench.REQUESTS,
            mixes=chaos_bench.QUICK_MIXES if args.quick else None)))
    if args.only in (None, "serve"):
        sections.append(("serve", lambda: serve_bench.run(
            requests=serve_bench.QUICK_REQUESTS if args.quick
            else serve_bench.REQUESTS,
            mixes=serve_bench.QUICK_MIXES if args.quick else None,
            repeats=1 if args.quick else 3)))
    if args.only in (None, "sketch"):
        sections.append(("sketch", lambda: sketch_bench.run(
            sizes=sketch_bench.QUICK_SIZES if args.quick else None,
            repeats=1 if args.quick else 3)))
    if args.only in (None, "sketchres"):
        sections.append(("sketchres", lambda: sketchres_bench.run(
            sizes=sketchres_bench.QUICK_SIZES if args.quick else None,
            repeats=1 if args.quick else 3,
            steps=4 if args.quick else sketchres_bench.STEPS,
            nnz=512 if args.quick else sketchres_bench.NNZ)))
    if args.only in (None, "roofline"):
        sections.append(("roofline-single", lambda: roofline.run(
            mesh="pod16x16")))
        sections.append(("roofline-multi", lambda: roofline.run(
            mesh="pod2x16x16")))

    failures = []
    results = {}
    for name, fn in sections:
        print(f"\n{'='*72}\n# {name}\n{'='*72}")
        try:
            out = fn()
            if isinstance(out, dict):
                results[name] = out
        except Exception as e:                      # noqa: BLE001
            failures.append((name, e))
            print(f"[bench] {name} FAILED: {e}")
    if args.emit_json:
        import jax
        dev = jax.devices()[0]
        payload = {"schema": "repro-bench/v1", "quick": args.quick,
                   "backend": jax.default_backend(),
                   "device": {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": jax.device_count()},
                   "sections": results}
        with open(args.emit_json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[bench] wrote {args.emit_json} "
              f"({len(results)} section(s))")
    print(f"\n[bench] done in {time.time()-t0:.0f}s; "
          f"{len(sections)-len(failures)}/{len(sections)} sections ok")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
