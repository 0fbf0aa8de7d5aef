"""Pallas kernels COMPILED on a TPU (interpret=False).

The rest of the suite pins the CPU backend (tests/conftest.py), so these
tests drive the chip from a subprocess with the default backend, and skip
when that finds no TPU (always, in the CPU sandbox). The numeric checks are
chip_smoke.py's kernel leg at its full sizes, which the chip machine runs
through ``python chip_smoke.py``; this file exists for a pytest run made on
a machine that has the chip:

- flash_attention_pallas vs the full-softmax reference (causal + offsets),
  including a context length whose K/V could never fit a per-program VMEM
  staging (the regression the grid-tiled kernel fixed);
- pa_scan_update vs the exact numpy sequential PA recurrence;
- the attention() entry point dispatching to Pallas by default on TPU.
"""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json
import jax

if jax.devices()[0].platform != "tpu":
    print(json.dumps({"skip": "no tpu"}))
    raise SystemExit(0)

import chip_smoke

print(json.dumps(chip_smoke._kernel_numerics(**chip_smoke.NUMERICS_FULL)))
"""


@pytest.fixture(scope="module")
def tpu_results():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, cwd=_ROOT, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    if "skip" in data:
        pytest.skip(data["skip"])
    return data


class TestPallasOnTPU:
    def test_flash_attention_matches_reference(self, tpu_results):
        # the QK^T dot rides the MXU at default (bf16-accumulated) precision
        assert tpu_results["flash_err_causal_False"] < 5e-3
        assert tpu_results["flash_err_causal_True"] < 5e-3
        assert tpu_results["flash_err_offset"] < 5e-3

    def test_flash_attention_long_context(self, tpu_results):
        assert tpu_results["longctx_finite"] is True

    def test_attention_entry_dispatches_pallas(self, tpu_results):
        assert tpu_results["entry_err"] < 5e-3

    def test_flash_backward_matches_reference_grads(self, tpu_results):
        assert tpu_results["bwd_err"] < 2e-2  # bf16 MXU dots in both passes

    def test_pa_scan_exact_recurrence(self, tpu_results):
        assert tpu_results["pa_w_err"] < 1e-4
        assert tpu_results["pa_loss_err"] < 1e-4
