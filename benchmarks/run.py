# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV; the JSON-instrumented benchmarks (fig3, kernels, budget) ALSO write
# machine-readable BENCH_*.json files to the repo root by default — the
# perf-trajectory artifacts the CI bench lane uploads (docs/scaling.md
# explains how to read them). --json-dir none disables the artifacts.
from __future__ import annotations

import argparse
import os
import sys
import time

# `python benchmarks/run.py` puts benchmarks/ itself on sys.path; the
# `from benchmarks import ...` imports below need the repo root.
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,fig45,fig3,budget,kernels,async,"
                         "lmfed,qopt,roofline")
    ap.add_argument("--fl-rounds", type=int, default=None,
                    help="fig3 round budget (default: the benchmark's own "
                         "full/smoke default; an explicit value wins even "
                         "with --smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI bench-lane budgets for the JSON-instrumented "
                         "benchmarks (fig3, budget)")
    ap.add_argument("--json-dir", default=REPO_ROOT, metavar="DIR",
                    help="where BENCH_*.json artifacts land (default: the "
                         "repo root, where the CI bench lane uploads them "
                         "from); 'none' disables JSON output")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    wanted = set(args.only.split(",")) if args.only else None
    json_dir = None if args.json_dir == "none" else args.json_dir

    def want(name):
        return wanted is None or name in wanted

    def json_path(name):
        return os.path.join(json_dir, name) if json_dir else None

    print("name,us_per_call,derived")
    t0 = time.time()
    violations = []
    errors = []

    def attempt(name, fn):
        # one broken benchmark must not abort the rest of the suite: the
        # completed BENCH_*.json artifacts still land, the failure is
        # collected, and the exit code stays nonzero at the end
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - isolate ANY bench failure
            errors.append(f"{name}: {type(e).__name__}: {e}")
            print(f"bench_error[{name}],0,{type(e).__name__}",
                  file=sys.stderr)
            return None

    if want("fig2"):
        from benchmarks import fig2_renyi

        attempt("fig2", fig2_renyi.run)
    if want("fig45"):
        from benchmarks import fig45_theta_sweep

        attempt("fig45", fig45_theta_sweep.run)
    if want("kernels"):
        from benchmarks import kernel_bench

        if json_dir:
            attempt("kernels", lambda: kernel_bench.bench_json(
                json_path("BENCH_kernels.json")))
        else:
            attempt("kernels", kernel_bench.run)
    if want("fig3"):
        from benchmarks import fig3_fl_emnist

        if json_dir:
            attempt("fig3", lambda: fig3_fl_emnist.bench_json(
                json_path("BENCH_fig3.json"),
                smoke=args.smoke, rounds=args.fl_rounds))
        else:
            rounds = args.fl_rounds or (fig3_fl_emnist.SMOKE_ROUNDS
                                        if args.smoke else fig3_fl_emnist.ROUNDS)
            attempt("fig3", lambda: fig3_fl_emnist.run(
                rounds=rounds,
                fed=fig3_fl_emnist.SMOKE_FED if args.smoke else None,
            ))
    if want("budget"):
        from benchmarks import fig_budget

        if json_dir:
            # the budget sweep always runs at the smoke budget here (the
            # full sweep is a standalone `python benchmarks/fig_budget.py`)
            violations = attempt("budget", lambda: fig_budget.bench_json(
                json_path("BENCH_budget.json"), smoke=True)) or []
        else:
            attempt("budget", lambda: fig_budget.run(
                targets=fig_budget.SMOKE_TARGETS,
                rounds=fig_budget.SMOKE_ROUNDS,
                fed=fig_budget.SMOKE_FED))
    if want("async"):
        import tempfile

        from benchmarks import fig_async

        # the async bench is tracker-instrumented end to end: without a
        # json dir it still runs, the artifact just lands in a tempdir
        path = (json_path("BENCH_async.json") if json_dir else
                os.path.join(tempfile.mkdtemp(), "BENCH_async.json"))
        attempt("async", lambda: fig_async.bench_json(path,
                                                      smoke=args.smoke))
    if want("lmfed"):
        import tempfile

        from benchmarks import fig_lmfed

        # tracker-instrumented end to end, like the async bench: without
        # a json dir the artifact lands in a tempdir
        path = (json_path("BENCH_lmfed.json") if json_dir else
                os.path.join(tempfile.mkdtemp(), "BENCH_lmfed.json"))
        attempt("lmfed", lambda: fig_lmfed.bench_json(path,
                                                      smoke=args.smoke))
    if want("qopt"):
        from benchmarks import beyond_qopt

        attempt("qopt", beyond_qopt.run)
    if want("roofline"):
        from benchmarks import roofline

        attempt("roofline", roofline.run)
    print(f"total_wall,{(time.time()-t0)*1e6:.0f},seconds={time.time()-t0:.1f}",
          file=sys.stderr)
    failures = errors + [f"budget contract: {v}" for v in violations]
    if failures:
        raise SystemExit(f"benchmarks failed ({len(failures)}): "
                         + "; ".join(failures))


if __name__ == "__main__":
    main()
