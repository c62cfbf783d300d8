"""``chip_smoke.py``'s phases at tiny sizes on the CPU.

The script itself runs only on a TPU; these tests call each phase
function the way ``main()`` does, with the kernels in interpret mode,
so a change that breaks a phase fails here first.
"""

import importlib.util
import pathlib

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_kernels_phase(smoke):
    assert smoke.phase_kernels(128 * 64, 66, interpret=True) \
        <= smoke.REDUCE_RTOL


def test_sweep_phase(smoke):
    assert smoke.phase_sweep(64) <= smoke.SOLVER_RTOL


def test_simulate_phase(smoke):
    assert smoke.phase_simulate(16, 16, 2) <= smoke.DESYNC_RTOL


def test_fit_phase(smoke):
    assert smoke.phase_fit(["DCOPY", "DDOT2"], ["CLX"], (0,),
                           n_events=2000) <= smoke.SOLVER_RTOL


def test_serve_phase(smoke):
    assert smoke.phase_serve(128) <= smoke.SOLVER_RTOL


def test_main_refuses_a_process_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""
