# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
#
# Mapping to the paper:
#   bench_linregr     — Figures 4/5 (linregr UDA scaling: k-sweep,
#                        implied segment speedup, v0.1 vs v0.3 history)
#   bench_iterative   — §4.2 IRLS cost + driver overhead; §4.3 k-means
#                        two-pass vs fused single pass
#   bench_profile     — §Table 1 profile: shared-scan fused aggregates
#                        (pass count + wall time) vs scan-per-aggregate
#   bench_plan        — §3.2 declarative batches: planned (scan-sharing
#                        optimizer) vs naive per-statement execution
#   bench_join        — star-schema joined GROUP BY: shared-sort
#                        sort-merge join vs per-statement
#                        gather-materialize
#   bench_ivm         — §4.1 merge combinators as incremental view
#                        maintenance: delta-fold refresh vs full rescan
#   bench_serve       — §3.2 serving: cross-session admission-window
#                        scan sharing + version-keyed result caching
#   bench_sgd_models  — Table 2 (six models, one SGD abstraction)
#   bench_text        — Table 3 (feature extraction, Viterbi, MCMC,
#                        q-gram matching)
#   roofline          — §Roofline rows from the dry-run artifacts (only
#                        emitted when results/dryrun exists)

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    from . import bench_ivm, bench_join, bench_linregr, bench_iterative, \
        bench_plan, bench_profile, bench_serve, bench_sgd_models, \
        bench_text, roofline

    suites = [
        ("bench_linregr", bench_linregr.run),
        ("bench_iterative", bench_iterative.run),
        ("bench_profile", bench_profile.run),
        ("bench_plan", bench_plan.run),
        ("bench_join", bench_join.run),
        ("bench_ivm", bench_ivm.run),
        ("bench_serve", bench_serve.run),
        ("bench_sgd_models", bench_sgd_models.run),
        ("bench_text", bench_text.run),
        ("roofline", roofline.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for suite_name, fn in suites:
        try:
            for name, us, extra in fn():
                print(f"{name},{us:.1f},{extra}", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{suite_name},NaN,ERROR:{type(e).__name__}:{e}",
                  flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
