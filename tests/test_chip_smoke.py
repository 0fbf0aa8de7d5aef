"""chip_smoke.py off the chip: every leg at a tiny size on the CPU (Pallas
kernels interpreted), and the script itself refusing to run without a TPU.
The real run is ``python chip_smoke.py`` on the chip machine."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402


def test_stream_fused_tiny(tmp_path):
    out = chip_smoke.leg_stream_fused(
        str(tmp_path), 2, batch=64, chain=2, launches=3, test_set=16
    )
    assert out["state_devices"] == [0, 1]
    assert out["fitted"] + out["holdout"] == out["rows"]


def test_stream_sparse_tiny(tmp_path):
    # the model is wider than a batch (64 x 41 entries), so that the leg's
    # static guard can tell a pass over the model from one over the batch
    out = chip_smoke.leg_stream_sparse(
        str(tmp_path), 1, hash_space=1 << 13, batch=64, launches=3,
        test_set=16,
    )
    assert out["model_width"] == 13 + (1 << 13)
    # the weights alone: Synchronous holds no est and no center beside them
    assert out["wide_passes"] == 0 and out["vector_leaves_aliased"] == 1


def test_stream_mixed_tiny(tmp_path):
    out = chip_smoke.leg_stream_mixed(
        str(tmp_path), 1, tenants=8, blocks=12, block_rows=64, forecasts=40,
        batch=16,
    )
    assert out["fitted"][0] > out["fitted"][-1] > 0
    assert all(n > 0 for n in out["programLaunches"])


def test_kernels_tiny():
    out = chip_smoke.leg_kernels(
        1,
        pa=dict(dim=8, batch=16, n_batches=2),
        lm=dict(vocab=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
                seq_len=32, batch=2, steps=3, bf16=False),
        numerics=dict(seq_len=64, long_len=256, heads=1, dh=8, pa_dim=5,
                      pa_batch=16),
    )
    assert out["pa_kernel_compiled"] is False  # interpreted off the chip
    assert out["lm_losses"][-1] < out["lm_losses"][0]


def test_a_failed_check_raises():
    with pytest.raises(RuntimeError, match="chip_smoke check failed"):
        chip_smoke._require(False, "an example")


def test_script_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, cwd=_ROOT, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert proc.stdout == ""  # no leg ran, no result line
    assert "platform='cpu'" in proc.stderr
