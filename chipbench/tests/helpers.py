"""Helpers of the benchmark's own tests (CPU, tiny cells)."""
import json
import pathlib
import shutil
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def make_root(tmp: pathlib.Path, copy: bool = False) -> pathlib.Path:
    """A checkout holding the tiny cells' BENCHMARK.json and chipbench/
    (linked, or copied where a test adds files to it)."""
    tmp.mkdir(parents=True, exist_ok=True)
    shutil.copy(DATA / "BENCHMARK.json", tmp / "BENCHMARK.json")
    if copy:
        shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    else:
        (tmp / "chipbench").symlink_to(ROOT / "chipbench")
    return tmp


def cpu_chip(n):
    """Stands in for the harness's look for a chip: the CPU device and the
    v5e's peaks (nothing measured here is a device number)."""
    import jax

    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    return jax.devices()[:n], peaks["TPU v5 lite"]


def run_cell(root, workload, seed=2**31 + 17, seconds=1.0, trace=False,
             control=False):
    """One tiny run on the CPU; returns (result, stdout lines).  JAX's
    caches are cleared first, so that the run traces its programs (and
    reports its dispatch path) as a fresh process would."""
    import jax

    from chipbench import bench

    jax.clear_caches()
    lines = []
    result = bench.run(workload, seed, seconds, trace, t_start=time.perf_counter(),
                       control=control, root=root, chip_check=cpu_chip,
                       out=lines.append, err=lambda s: None)
    return result, lines


def line(lines, prefix):
    (hit,) = [s for s in lines if s.startswith(prefix)]
    return json.loads(hit[len(prefix):])
