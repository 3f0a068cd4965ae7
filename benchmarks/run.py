"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows and writes a ``BENCH_*.json``
snapshot (the perf trajectory CI tracks).

  Fig 1   -> bench_ddl_allreduce   (DDL vs flat all-reduce; overlapped row)
  Fig 2b  -> bench_lms_overhead    (LMS overhead vs problem scale)
  Tab 1/Fig 3 -> bench_scaling     (DP scaling, modeled + measured)
  Tab 2 / s3.1 -> bench_accuracy_parity (convergence parity)
  kernels -> bench_kernels         (hot-spot microbenchmarks)
  serving -> bench_serve           (engine vs static batch; measured)

``--smoke`` runs the fast analytic tables plus the one small measured row
the residency-execution gate needs (streamed-optimizer vs resident, a
smoke-config jit on one device) and writes BENCH_smoke.json — the CI gate.
Either mode fails (exit 1) if any bench module does not import: a bench
that silently stops importing would otherwise just vanish from the
trajectory.
"""
import argparse
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _import_modules():
    """Import every bench module up front; an ImportError anywhere is fatal
    (exit 1), not a silently shrunk benchmark table."""
    import importlib
    names = ["bench_ddl_allreduce", "bench_lms_overhead", "bench_scaling",
             "bench_kernels", "bench_accuracy_parity", "bench_serve"]
    mods = {}
    failures = []
    for n in names:
        try:
            mods[n] = importlib.import_module(f"benchmarks.{n}")
        except Exception as e:
            failures.append((n, e))
            traceback.print_exc()
    if failures:
        for n, e in failures:
            print(f"IMPORT-FAILED,{n},{type(e).__name__}: {e}",
                  file=sys.stderr)
        sys.exit(1)
    return mods


def compare_rows(fresh_rows, baseline_path, *, tol: float,
                 min_us: float) -> int:
    """Bench regression gate: diff the fresh rows against a committed
    baseline snapshot keyed by (table, name); any step-time/tok-s row slower
    than `tol` x its baseline fails. Rows under `min_us` in the baseline are
    exempt (timer jitter dominates them); rows only on one side are warned
    about, never failed — renames and new benches must not brick CI.

    Re-baseline (after an intentional perf change, on the CI machine class):
        python benchmarks/run.py --smoke --out benchmarks/BENCH_baseline.json
    """
    with open(baseline_path) as f:
        base = {(r["table"], r["name"]): r for r in json.load(f)["rows"]}
    fresh = {(r["table"], r["name"]): r for r in fresh_rows}
    failures = []
    for key, b in sorted(base.items()):
        r = fresh.get(key)
        if r is None:
            print(f"COMPARE-MISSING,{key[0]}/{key[1]},baseline row not in "
                  "fresh run", file=sys.stderr)
            continue
        if b["us_per_call"] < min_us:
            continue
        ratio = r["us_per_call"] / max(b["us_per_call"], 1e-9)
        status = "REGRESSED" if ratio > tol else "ok"
        print(f"compare,{key[0]}/{key[1]},{b['us_per_call']:.1f}us->"
              f"{r['us_per_call']:.1f}us,{ratio:.2f}x,{status}")
        if ratio > tol:
            failures.append((key, ratio))
    for key in sorted(set(fresh) - set(base)):
        print(f"COMPARE-NEW,{key[0]}/{key[1]},not in baseline (re-baseline "
              "to start tracking)", file=sys.stderr)
    if failures:
        for key, ratio in failures:
            print(f"COMPARE-FAILED,{key[0]}/{key[1]},{ratio:.2f}x slower "
                  f"(tol {tol:.2f}x)", file=sys.stderr)
    return len(failures)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast analytic benches only; writes BENCH_smoke.json")
    ap.add_argument("--out", default=None,
                    help="override the BENCH json path")
    ap.add_argument("--compare", default=None, metavar="BASELINE_JSON",
                    help="diff the fresh rows against a committed baseline "
                         "snapshot; exit 1 on any >tol slowdown")
    ap.add_argument("--compare-tol", type=float, default=1.25,
                    help="slowdown ratio that fails the gate (default 1.25 "
                         "= 25%% slower)")
    ap.add_argument("--compare-min-us", type=float, default=100.0,
                    help="skip rows whose baseline is faster than this "
                         "(timer jitter dominates)")
    ap.add_argument("--compare-mode", choices=("gate", "warn"),
                    default="gate",
                    help="warn: report regressions without failing — for a "
                         "new machine class whose baseline has not been "
                         "re-recorded yet")
    ap.add_argument("--obs-report", default=None,
                    help="override the obs_report.json path (written next "
                         "to the BENCH json by default)")
    ap.add_argument("--trace", default="",
                    help="run the bench modules under the JAX profiler, "
                         "writing its trace (host spans and device ops on "
                         "one clock) under this directory")
    args = ap.parse_args()

    from repro.launch.cache import configure_compile_cache
    configure_compile_cache()
    b = _import_modules()
    if args.smoke:
        modules = [
            ("fig1", b["bench_ddl_allreduce"].run),
            ("fig2b", b["bench_lms_overhead"].run),
            ("fig2bc", b["bench_lms_overhead"].run_calibrated),
            ("fig2bo", b["bench_lms_overhead"].run_opt_stream_measured),
            ("tab1", b["bench_scaling"].run),
            ("serve", b["bench_serve"].run),
        ]
    else:
        # the CPU-emulation tables (fig1m, tab1m, tab2: child interpreters
        # pinned to JAX_PLATFORMS=cpu) run first, before this process
        # initializes a backend and takes the chip
        modules = [
            ("fig1m", b["bench_ddl_allreduce"].run_measured),
            ("tab1m", b["bench_scaling"].run_measured),
            ("tab2", b["bench_accuracy_parity"].run),
            ("fig1", b["bench_ddl_allreduce"].run),
            ("fig2b", b["bench_lms_overhead"].run),
            ("fig2bc", b["bench_lms_overhead"].run_calibrated),
            ("fig2bm", b["bench_lms_overhead"].run_measured),
            ("fig2bo", b["bench_lms_overhead"].run_opt_stream_measured),
            ("tab1", b["bench_scaling"].run),
            ("kern", b["bench_kernels"].run),
            ("serve", b["bench_serve"].run),
        ]
    print("name,us_per_call,derived")
    rows, failures = [], 0
    from repro.obs import get_obs, profiler_trace, write_obs_report
    with profiler_trace(args.trace):
        for tag, fn in modules:
            try:
                for r in fn():
                    print(f"{r['name']},{r['us_per_call']:.1f},"
                          f"{r['derived']}")
                    rows.append({"table": tag, **{k: r[k] for k in
                                                  ("name", "us_per_call",
                                                   "derived")}})
            except Exception as e:
                failures += 1
                print(f"{tag}_FAILED,0,{type(e).__name__}: {e}",
                      file=sys.stderr)
                traceback.print_exc()
    if args.trace:
        print(f"wrote a profiler trace under {args.trace}", file=sys.stderr)
    out = args.out or os.path.join(
        REPO, f"BENCH_{'smoke' if args.smoke else 'full'}.json")
    with open(out, "w") as f:
        json.dump({"mode": "smoke" if args.smoke else "full",
                   "unix_time": int(time.time()),  # lint: waive RL001 record stamp is wall-clock by design
                   "failures": failures,
                   "rows": rows}, f, indent=1)
    print(f"wrote {out} ({len(rows)} rows)", file=sys.stderr)
    # observability sidecar (DESIGN.md §12): the swap/compute/collective
    # span timeline every bench module recorded into the global ring,
    # reduced to per-step overlap_frac + per-residency-class swap bytes —
    # the report Planner v2 consumes alongside analysis_report.json
    obs_path = args.obs_report or os.path.join(
        os.path.dirname(out) or ".", "obs_report.json")
    write_obs_report(obs_path, obs=get_obs(),
                     meta={"mode": "smoke" if args.smoke else "full"})
    print(f"wrote {obs_path}", file=sys.stderr)
    if failures:
        sys.exit(1)
    if args.compare:
        regressions = compare_rows(rows, args.compare, tol=args.compare_tol,
                                   min_us=args.compare_min_us)
        if regressions and args.compare_mode == "gate":
            sys.exit(1)
        if regressions:
            print(f"compare-mode=warn: {regressions} regression(s) NOT "
                  "failing the run — re-record the baseline on this "
                  "machine class", file=sys.stderr)


if __name__ == "__main__":
    main()
