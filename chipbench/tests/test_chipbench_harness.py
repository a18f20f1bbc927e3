"""Discovery of files dropped in by name, and the harness's refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.tests.helpers import ROOT, line, make_root, run_cell


def test_new_config_mix_and_metric_are_found(tmp_path, monkeypatch):
    """A cell that names a new configuration, mix and per-layer metric
    runs from those files alone: nothing else is edited."""
    root = make_root(tmp_path, copy=True)
    cb = root / "chipbench"
    cfg = json.loads((cb / "tests/data/tiny-er.json").read_text())
    cfg.update(name="brand-new")
    cfg["deployment"].update(n_nodes=16, tenants=3)
    (cb / "configs/brand-new.json").write_text(json.dumps(cfg))
    (cb / "arrivals/poisson_new.py").write_text(
        "def factors(demand, n_tenants, rng):\n"
        "    r = demand['requests_per_interval']\n"
        "    return rng.poisson(r, (demand['horizon'], n_tenants)) / r\n")
    (cb / "traffic/bursty-new.json").write_text(json.dumps({
        "why": "test", "grad_policy": "sampled",
        "demand": {"kind": "poisson_new", "requests_per_interval": 25,
                   "horizon": 64}}))
    (cb / "metrics/intervals_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx['intervals']))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "brand-new", "source": "test",
                             "file": "chipbench/configs/brand-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "brand-new.bursty",
                               "config": "brand-new",
                               "traffic": "bursty-new", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "intervals_seen", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "serve host path",
                               "moves": "interval_ms",
                               "workloads": ["brand-new.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the profiler stops early, so that intervals run after it (what
    # p95_interval_ms reads): collecting the trace takes seconds here
    monkeypatch.setattr("chipbench.bench.TRACE_SECONDS", 0.3)
    result, lines = run_cell(root, "brand-new.bursty", trace=True,
                             seconds=6.0)
    assert result["correct"]
    # the new arrival process changed the demand, and the reference
    # followed each change
    assert line(lines, "window: ")["demand_changes"] > 0
    assert result["metrics"]["intervals_seen"]["value"] == \
        result["attempted"]
    assert "measure_ms" in result["metrics"]
    assert result["metrics"]["p95_interval_ms"]["value"] > 0
    # a metric that finds nothing to read is left out, never reported as 0
    assert "device_idle_pct" not in result["metrics"]


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "cec-paper.k1024.sampled", "--seed", "2147483999", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_py_refuses_without_a_tpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_run_py_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ cannot run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


@pytest.mark.parametrize("kind", ["cpu", "unknown"])
def test_chip_check_refuses(monkeypatch, kind):
    import jax

    from chipbench import bench

    class Dev:
        platform = "cpu" if kind == "cpu" else "tpu"
        device_kind = "TPU v0 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(bench.NoChip):
        bench.require_chip(1)


def test_config_without_limits_is_refused(tmp_path):
    """A configuration whose comparison has no limits set from readings
    cannot run a cell: nothing would be judged."""
    root = make_root(tmp_path, copy=True)
    cfg_path = root / "chipbench/tests/data/tiny-er.json"
    cfg = json.loads(cfg_path.read_text())
    del cfg["check"]["limits"]
    cfg_path.write_text(json.dumps(cfg))
    with pytest.raises(SystemExit, match="no limits"):
        run_cell(root, "tiny-er.sampled", seconds=0.2)
