"""CLI for the perf-fingerprint regression gate (tools/perfdiff).

    python -m tools.perfdiff                     # run + compare
    python -m tools.perfdiff --current run.json  # compare a recorded run
    python -m tools.perfdiff --write-baseline    # regenerate baseline
    python -m tools.perfdiff --baseline other.json

Exit status: 0 = fingerprint within baseline, 1 = regression (or a
baseline/schema problem). The run path forces JAX_PLATFORMS=cpu so the
canonical workload's exact fields stay machine-independent.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser(prog="tools.perfdiff")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: PERF_BASELINE.json)")
    ap.add_argument("--current", default=None,
                    help="compare this recorded fingerprint instead of "
                         "running the canonical workload")
    ap.add_argument("--write-baseline", action="store_true",
                    help="run the canonical workload and (re)write the "
                         "baseline file")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from tools import perfdiff

    path = args.baseline or perfdiff.BASELINE_PATH
    if args.write_baseline:
        fp = perfdiff.run_canonical_workload()
        with open(path, "w") as f:
            json.dump(fp, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")
        return 0

    if args.current:
        with open(args.current) as f:
            current = json.load(f)
    else:
        current = perfdiff.run_canonical_workload()

    baseline = perfdiff.load_baseline(path)
    failures = perfdiff.compare(baseline, current)
    if failures:
        print("PERF REGRESSION vs", path)
        for f_ in failures:
            print("  -", f_)
        return 1
    print(f"perf fingerprint OK vs {path} "
          f"({len(baseline.get('exact', {}))} exact, "
          f"{len(baseline.get('noisy', {}))} banded metrics)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
