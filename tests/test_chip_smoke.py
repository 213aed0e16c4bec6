"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and each
of its phases runs and passes its own checks on tiny shapes (kernels in
interpret mode, the region on one device)."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

SMALL = chip_smoke.SMALL


@pytest.mark.parametrize("argv", [[], ["--region"]])
def test_refuses_to_run_without_a_tpu(argv, capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(argv, size=SMALL)
    assert "no TPU chip" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
    # Refused before placing the compile cache: tests never write to it.
    assert jax.config.jax_compilation_cache_dir == cache_dir


@pytest.mark.pallas
def test_kernel_phase_interpret_mode():
    diffs = chip_smoke.phase_kernels(SMALL, force="pallas")
    assert diffs["pdu_health_sim[slew_health].soc_path"] == 0.0
    assert diffs["pdu_health_sim[ess_events].grid"] <= chip_smoke.GRID_TOL
    assert max(diffs[f"admm_iterate.{v}"] for v in "xzy") <= chip_smoke.ADMM_TOL


def test_campus_phase():
    out = chip_smoke.phase_campus(SMALL)
    assert out["campus_grid"] <= chip_smoke.ENGINE_GRID_TOL


@pytest.mark.service
def test_service_phase_resumes_bitwise(tmp_path):
    chip_smoke.phase_service(SMALL, str(tmp_path))
    assert os.path.exists(tmp_path / "service.npz")


@pytest.mark.grid
def test_region_phase_on_one_device():
    out = chip_smoke.phase_region(SMALL, 1)
    assert out["campus_rack"] == 0.0
