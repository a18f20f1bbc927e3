"""Coverage floor gate for the solver-facing packages (CI bench-smoke job).

Reads a ``coverage.json`` produced by ``pytest --cov=repro
--cov-report=json``, prints a per-file summary for each gated package,
and fails when any package's aggregate line coverage drops below its
recorded floor.

Gated packages:

* ``src/repro/core/`` — the control-plane core, the fleet-mesh helpers
  ``run_batch_sharded`` rides on (``core/mesh.py``, DESIGN.md §14)
  included; floor recorded at PR 4 (the sparse-engine PR that
  introduced this gate).
* ``src/repro/obs/`` — the observability subsystem (ISSUE 10, DESIGN.md
  §18): telemetry rings, paper-invariant monitors, trace/JSONL export.
  Pure host-visible code with a dedicated suite (tests/test_obs.py);
  floor 85%.

Floors are *minus a small flake margin* under what the suite measures.
Policy: ratchet them upward as coverage grows; never lower one to make a
PR pass — delete the untested code or test it.  Override for local
experiments only: ``REPRO_CORE_COV_MIN=<percent>`` /
``REPRO_OBS_COV_MIN=<percent>``.

Usage:  python scripts/check_core_coverage.py [coverage.json]
"""
from __future__ import annotations

import json
import os
import pathlib
import sys

# (path marker, recorded floor %, local-override env var); keep in sync
# with reality by ratcheting, not lowering.
GATES = (
    ("repro/core/", 80.0, "REPRO_CORE_COV_MIN"),
    # the observability subsystem (ISSUE 10, DESIGN.md §18): rings,
    # monitors, exporters are all host-visible pure code, so the tier-1
    # suite should cover nearly all of it — gated at 85%.
    ("repro/obs/", 85.0, "REPRO_OBS_COV_MIN"),
)

# per-file floors for the differentiable-core modules (PR 8): the implicit
# VJP and the hypergradient loop are correctness-critical math whose
# failure mode is a silently wrong gradient, so they carry their own bar
# on top of the package aggregate.  The control megakernel (PR 9,
# DESIGN.md §17) joins them: its kernel body runs as Python under
# interpret mode on the CI backend, so pytest-cov sees every executed
# line — measured 100% under the tier-1 suite, gated at 90% flake margin.
FILE_GATES = (
    ("repro/core/implicit.py", 85.0),
    ("repro/core/hypergrad.py", 85.0),
    ("repro/kernels/control_megakernel.py", 90.0),
)


def _gate(data: dict, marker: str, floor: float) -> int:
    rows = []
    covered = statements = 0
    for fname, info in sorted(data["files"].items()):
        if marker not in fname.replace("\\", "/"):
            continue
        s = info["summary"]
        covered += s["covered_lines"]
        statements += s["num_statements"]
        rows.append((fname, s["num_statements"], s["covered_lines"],
                     s["percent_covered"]))
    if not statements:
        print(f"error: no files matching '{marker}'", file=sys.stderr)
        return 2

    print(f"{'file':58s} {'stmts':>6s} {'cover':>6s} {'pct':>7s}")
    for fname, n, c, pct in rows:
        print(f"{fname:58s} {n:6d} {c:6d} {pct:6.1f}%")
    total = 100.0 * covered / statements
    print(f"{'TOTAL src/' + marker:58s} {statements:6d} {covered:6d} "
          f"{total:6.1f}%  (floor {floor:.1f}%)")

    if total < floor:
        print(f"FAIL: {marker} coverage {total:.1f}% is below the recorded "
              f"floor {floor:.1f}% — add tests (or, for a deliberate "
              "removal of tested code, ratchet consciously in "
              "scripts/check_core_coverage.py with a commit-message note)",
              file=sys.stderr)
        return 1
    return 0


def _file_gate(data: dict, marker: str, floor: float) -> int:
    for fname, info in data["files"].items():
        if marker in fname.replace("\\", "/"):
            pct = info["summary"]["percent_covered"]
            print(f"{fname:58s} {pct:6.1f}%  (file floor {floor:.1f}%)")
            if pct < floor:
                print(f"FAIL: {marker} coverage {pct:.1f}% is below its "
                      f"per-file floor {floor:.1f}%", file=sys.stderr)
                return 1
            return 0
    print(f"error: no file matching '{marker}' in coverage data",
          file=sys.stderr)
    return 2


def main(path: str = "coverage.json") -> int:
    data = json.loads(pathlib.Path(path).read_text())
    rc = 0
    for marker, default_floor, env in GATES:
        floor = float(os.environ.get(env, default_floor))
        rc = max(rc, _gate(data, marker, floor))
        print()
    for marker, floor in FILE_GATES:
        rc = max(rc, _file_gate(data, marker, floor))
    return rc


if __name__ == "__main__":
    raise SystemExit(main(*sys.argv[1:]))
