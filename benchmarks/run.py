"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig7,...] [--smoke]``
prints ``name,us_per_call,derived`` CSV rows and writes JSON artifacts to
experiments/paper/ (consumed by EXPERIMENTS.md).

``--smoke`` is the CI gate (tiny sizes, 1 warmup / 1 iter — see
``common.set_smoke``): it exercises every module's kernel and batch paths
end-to-end, writes a ``BENCH_smoke.json`` summary at the repo root (the
uploaded CI artifact), and exits non-zero on any import or runtime error.
It additionally appends one *perf-trajectory* entry per commit under
``benchmarks/trajectory/BENCH_<shortsha>.json`` (stable schema: commit,
commit date, per-bench median latency) — entries are committed with the
PR that produced them, so the trajectory accumulates across PRs instead
of one file being overwritten in place.  Smoke numbers are execution
proofs for trend eyeballing, never perf claims.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import statistics
import subprocess
import sys
import time
import traceback

from . import common

MODULES = ("fig7_routing_convergence", "fig8_9_network_size",
           "fig10_utility_functions", "fig11_single_loop",
           "table2_topologies", "bench_kernels", "bench_batched",
           "bench_scenarios", "bench_router", "bench_sparse",
           "bench_fleet", "bench_serving", "bench_learned",
           "bench_megakernel", "bench_obs", "perf_iterations")

TRAJECTORY_DIR = pathlib.Path("benchmarks/trajectory")
TRAJECTORY_SCHEMA = 3


def _git(*args: str) -> str:
    try:
        return subprocess.run(["git", *args], capture_output=True,
                              text=True, check=True).stdout.strip()
    except Exception:  # noqa: BLE001 — detached/dirty/missing git all OK
        return "unknown"


def _tree_dirty() -> bool:
    """Uncommitted changes beyond the bench artifacts themselves."""
    status = _git("status", "--porcelain")
    if status == "unknown":
        return True
    return any(
        line and "BENCH_smoke.json" not in line
        and "benchmarks/trajectory/" not in line
        for line in status.splitlines())


def write_trajectory_entry(summary: dict) -> pathlib.Path:
    """One BENCH_<shortsha>.json per commit so the trajectory accumulates.

    Schema (stable across PRs — consumers may rely on these keys):
      schema: int, commit: str, date: str (commit ISO date), dirty: bool
      (worktree had non-artifact changes beyond ``commit`` when measured),
      smoke: bool, jax/backend/python: str, benches: {module: {status,
      seconds, med_latency_us|None}} — ``med_latency_us`` is the median
      over the module's emitted CSV rows.  Only full runs write an entry
      (``--only`` subsets would masquerade as a complete record).

    Schema 2 (additive): a module that sets ``TRAJECTORY_ROWS = True``
    keeps its per-row records under ``benches.<module>.rows`` — e.g.
    ``bench_serving``'s p50/p99 control-interval latency per churn trace
    (README "Perf trajectory" documents how to read them).  Every other
    module still has its rows stripped to keep entries small.

    Schema 3 (additive): ``dirty`` and ``jax_version`` are first-class,
    always-present keys (``jax`` stays as the legacy alias).  Consumers
    must go through :func:`read_trajectory`, which back-fills both on
    schema-1/2 rows instead of KeyError-ing on history.
    """
    import jax

    commit = _git("rev-parse", "--short", "HEAD")
    entry = {
        "schema": TRAJECTORY_SCHEMA,
        "commit": commit,
        "date": _git("show", "-s", "--format=%cI", "HEAD"),
        "dirty": _tree_dirty(),
        "smoke": common.SMOKE,
        "python": platform.python_version(),
        "jax": jax.__version__,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "benches": summary,
    }
    TRAJECTORY_DIR.mkdir(parents=True, exist_ok=True)
    path = TRAJECTORY_DIR / f"BENCH_{commit}.json"
    path.write_text(json.dumps(entry, indent=1, default=str))
    return path


def read_trajectory(directory: pathlib.Path | str = TRAJECTORY_DIR
                    ) -> list[dict]:
    """Load every trajectory entry, oldest first, schema-tolerantly.

    Pre-schema-3 rows lack the first-class ``dirty``/``jax_version``
    keys; rather than make every consumer special-case history, this
    reader back-fills them (``jax_version`` from the legacy ``jax`` key,
    ``dirty`` conservatively ``True`` when a row predates the flag) and
    guarantees ``benches`` exists.  Newer keys pass through untouched —
    the schema only ever grows.
    """
    entries = []
    for path in sorted(pathlib.Path(directory).glob("BENCH_*.json")):
        entry = json.loads(path.read_text())
        entry.setdefault("jax_version", entry.get("jax", "unknown"))
        entry.setdefault("dirty", True)
        entry.setdefault("benches", {})
        entries.append(entry)
    entries.sort(key=lambda e: e.get("date", ""))
    return entries


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, 1 warmup/1 iter; write BENCH_smoke.json"
                         " + a benchmarks/trajectory/ entry")
    args = ap.parse_args()
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    common.set_smoke(args.smoke)

    print("name,us_per_call,derived")
    failed, summary = [], {}
    for mod in MODULES:
        if only and not any(mod.startswith(o) for o in only):
            continue
        t0 = time.perf_counter()
        n_records = len(common.RECORDS)
        try:
            m = __import__(f"benchmarks.{mod}", fromlist=["main"])
            rows = m.main()
            lat = [s for _, s in common.RECORDS[n_records:]]
            summary[mod] = {"status": "ok",
                            "seconds": round(time.perf_counter() - t0, 3),
                            "med_latency_us":
                                round(statistics.median(lat) * 1e6, 1)
                                if lat else None,
                            "rows": rows if isinstance(rows, (list, dict))
                            else None}
        except Exception as e:  # noqa: BLE001
            failed.append((mod, repr(e)))
            summary[mod] = {"status": "error", "error": repr(e),
                            "med_latency_us": None,
                            "seconds": round(time.perf_counter() - t0, 3)}
            traceback.print_exc()

    if args.smoke:
        import jax

        out = {"smoke": True, "python": platform.python_version(),
               "jax": jax.__version__, "backend": jax.default_backend(),
               "modules": summary,
               "failed": [m for m, _ in failed]}
        pathlib.Path("BENCH_smoke.json").write_text(
            json.dumps(out, indent=1, default=str))
        print(f"wrote BENCH_smoke.json ({len(summary)} modules, "
              f"{len(failed)} failed)", file=sys.stderr)
        if not only:        # a --only subset is not a trajectory point
            def _keeps_rows(mod: str) -> bool:
                m = sys.modules.get(f"benchmarks.{mod}")
                return bool(getattr(m, "TRAJECTORY_ROWS", False))

            traj = write_trajectory_entry(
                {mod: (s if _keeps_rows(mod)
                       else {k: v for k, v in s.items() if k != "rows"})
                 for mod, s in summary.items()})
            print(f"wrote {traj}", file=sys.stderr)

    if failed:
        print("FAILED:", failed, file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
