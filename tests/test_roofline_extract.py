"""roofline/extract contracts: import purity + control-kernel rows.

The import-time ``XLA_FLAGS`` mutation this module used to perform
(``--xla_force_host_platform_device_count=512``) poisoned every later
jax user in the process — any benchmark or test that imported the
roofline after a clean start suddenly ran the CPU backend with 512 fake
devices.  These tests pin the clean import, plus the control-kernel
cost-extraction surface the megakernel bench publishes to the perf
trajectory.
"""
import json
import os
import subprocess
import sys

import pytest


def test_import_leaves_environment_untouched():
    """Importing the module in a fresh interpreter must not create or
    edit XLA_FLAGS (the regression this file exists for)."""
    code = (
        "import os\n"
        "before = os.environ.get('XLA_FLAGS')\n"
        "import repro.roofline.extract\n"
        "assert os.environ.get('XLA_FLAGS') == before, os.environ.get("
        "'XLA_FLAGS')\n"
        "assert 'xla_force_host_platform_device_count' not in "
        "os.environ.get('XLA_FLAGS', '')\n"
        "print('clean')\n")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, cwd=".")
    assert r.returncode == 0, r.stderr
    assert "clean" in r.stdout


def test_import_does_not_multiply_devices():
    """The concrete symptom of the old side effect: a fresh process that
    imports the roofline then initialises jax must see the real device
    count, not 512 fakes."""
    code = (
        "import repro.roofline.extract\n"
        "import jax\n"
        "assert jax.device_count() < 512, jax.device_count()\n"
        "print('devices', jax.device_count())\n")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, cwd=".")
    assert r.returncode == 0, r.stderr


@pytest.fixture(scope="module")
def control_costs():
    from repro.roofline import extract

    return extract.control_step_costs(n_nodes=8, n_sessions=2, k_iters=1)


def test_control_step_costs_schema(control_costs):
    for variant in ("megakernel", "stitched"):
        rec = control_costs[variant]
        assert rec["flops"] > 0 and rec["bytes"] > 0
        assert rec["intensity"] == pytest.approx(
            rec["flops"] / rec["bytes"])
    shape = control_costs["shape"]
    assert shape["n_sessions"] == 2 and shape["k_iters"] == 1
    assert shape["phi_dtype"] == "float32"


def test_control_costs_restore_dispatch_env(control_costs):
    """Cost extraction temporarily forces the megakernel + φ dtype; both
    overrides must be unwound (the §17.4 knobs are process-global)."""
    from repro.core import dispatch

    assert "REPRO_MEGAKERNEL_PHI_DTYPE" not in os.environ
    assert not dispatch._megakernel_explicit()


def test_control_roofline_rows_schema(control_costs):
    from repro.roofline import extract
    from repro.roofline.analysis import HBM_BW, PEAK_FLOPS

    rows = extract.control_roofline_rows(control_costs)
    by_metric = {r["metric"]: r for r in rows}
    ridge = PEAK_FLOPS / HBM_BW
    for variant in ("megakernel", "stitched"):
        r = by_metric[f"roofline.control_step.{variant}"]
        assert r["ridge_flop_per_byte"] == pytest.approx(ridge)
        assert r["bound"] in ("compute", "memory")
        assert 0.0 <= r["attained_peak_fraction"] <= 1.0
        json.dumps(r)          # trajectory rows must be JSON-serializable
    assert "roofline.control_step.bytes_ratio" in by_metric
