#!/usr/bin/env python3
"""Chip benchmark of the CEC control plane: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``BENCHMARK.json``: ``workloads``) names a configuration
(``chipbench/configs/<config>.json``: the deployment, the builder that
draws it, the entry point that serves it, the solver's literals and the
limits of the comparison) and a traffic mix (``chipbench/traffic/
<mix>.json``).  The run builds the deployment and the demand trace from
the seed, warms up every shape, then runs control intervals back to back
for ``--seconds``: hand the entry point the interval's demand where the
mix changes it, call its ``control_step`` with the benchmark's utility
callback, and wait until the published Λ and replica weights are ready.
It prints the dispatch paths reached and the compilations inside the
window on earlier lines, then one JSON object: with ``--trace 0`` the
end-to-end metrics, with ``--trace 1`` the per-layer metrics from a
profiler trace of the window.  ``correct``
compares checked intervals with the plain reference
(``chipbench/reference.py``).  Without a TPU it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="judge the lower-precision control in the "
                         "program's place, so that `correct` comes out "
                         "false; the program's gaps are still printed "
                         "(limit-setting runs; the benchmark's own runs do "
                         "not use it)")
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="write the traced window's device and host events "
                         "as JSON (how the reduction's fixture is recorded)")
    args = ap.parse_args(argv)

    # one compile cache at a fixed path inside the checkout, given to the
    # program too (its own helper reads this variable)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import bench

    try:
        bench.run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=T_START, control=args.control,
                  keep_trace=args.keep_trace)
    except bench.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
