"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines; ``--json PATH`` additionally
writes the rows as a machine-readable artifact (the CI bench-smoke job
uploads it so the perf trajectory accumulates per commit). ``--tiny``
shrinks problem sizes / iteration counts for shared runners.

CPU wall numbers are relative only; every benchmark derives the TPU v5e
roofline projection used by EXPERIMENTS.md (this container has no TPU).
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="bench-smoke mode: tiny configs, 2 timed iters")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact")
    ap.add_argument("--only", default=None, metavar="NAMES",
                    help="comma-separated module suffixes to run")
    args = ap.parse_args()
    if args.tiny:
        os.environ["REPRO_BENCH_TINY"] = "1"   # before benchmarks import

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, root)       # `import benchmarks` as a namespace pkg
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (common, dfa_throughput, elastic_recovery,
                            fig6_resources, fig8_message_rate,
                            fig9_gdr_vs_staged, gather_scaling,
                            ingest_scaling, roofline, serving_latency,
                            streaming_periods, table1_logstar)
    mods = [fig6_resources, table1_logstar, fig8_message_rate,
            fig9_gdr_vs_staged, dfa_throughput, streaming_periods,
            serving_latency, elastic_recovery, gather_scaling,
            ingest_scaling, roofline]
    if args.only:
        keep = {m.strip() for m in args.only.split(",")}
        known = {m.__name__.split(".")[-1] for m in mods}
        unknown = keep - known
        if unknown:
            sys.exit(f"--only: unknown module(s) {sorted(unknown)}; "
                     f"known: {sorted(known)}")
        mods = [m for m in mods if m.__name__.split(".")[-1] in keep]

    print("name,us_per_call,derived")
    failures = []
    for mod in mods:
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append(mod.__name__)
            print(f"{mod.__name__},nan,ERROR={type(e).__name__}:{e}")
            traceback.print_exc()

    if args.json:
        common.write_artifact(args.json, failures=failures, tag="run")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
