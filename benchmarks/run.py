"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` style CSV per section.

  PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


SECTIONS = [
    ("micro snapshot (Fig. 9)", "benchmarks.micro_snapshot"),
    ("weak scaling (§6.2a)", "benchmarks.weak_scaling"),
    ("strong scaling (Figs. 10-11)", "benchmarks.strong_scaling"),
    ("restart/recompute (§6.2)", "benchmarks.recovery"),
    ("optimal intervals (Appx. A)", "benchmarks.intervals"),
    ("failure-scenario sweep + survival (Fig. 8)",
     "benchmarks.failure_sweep"),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    failures = 0
    for title, mod_name in SECTIONS:
        if args.only and args.only not in mod_name:
            continue
        print(f"\n=== {title} ===", flush=True)
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            import inspect
            if inspect.signature(mod.main).parameters:
                mod.main([])          # don't leak our argv into theirs
            else:
                mod.main()
            print(f"--- ok ({time.time()-t0:.1f}s)", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"--- FAILED ({time.time()-t0:.1f}s)", flush=True)
    print(f"\nbenchmarks done, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
