"""Calibration: known-parameter recovery + the regression envelope gate.

Two promises (repro.traces.calibrate):

* **Recovery.** Fitting the frontier plant against telemetry generated
  by a *known-parameter* plant (the committed
  ``tests/data/calibration/telemetry.npz``, truth stored as ``true_*``
  keys) recovers every fitted parameter within the documented tolerance
  — 2% for ``ua_w_k`` / ``tau_hx_s`` and ``basin_margin_c`` (the actual
  fixture errors are 0.05% / 0.27% / 0.00%; the tolerance leaves room
  for toolchain jitter, not physics drift).
* **Regression gate.** The committed ``fitted_params.json`` must keep
  reproducing the committed telemetry: ``check_envelope`` re-simulates
  with the committed parameters and fails if any channel's RMSE widens
  beyond the 5% numerical slack. A cooling-model change that silently
  degrades calibration fails tier-1 here.
"""
import json

import numpy as np
import pytest

import repro.traces.calibrate as cal
from conftest import DATA_DIR
from repro.systems.config import SYSTEMS
from repro.traces import TraceError

CAL_DIR = DATA_DIR / "calibration"
# documented recovery tolerance (relative) per fitted parameter
RECOVERY_RTOL = {"ua_w_k": 0.02, "tau_hx_s": 0.02, "basin_margin_c": 0.02}


@pytest.fixture(scope="module")
def telemetry():
    z = np.load(CAL_DIR / "telemetry.npz", allow_pickle=False)
    return {k: z[k] for k in z.files}


@pytest.fixture(scope="module")
def fitted():
    return cal.FittedParams.load(CAL_DIR / "fitted_params.json")


def _obs(tel):
    return {ch: tel[ch] for ch in ("t_basin_c", "t_supply_c",
                                   "t_return_c", "pue")}


def test_known_parameter_recovery(telemetry):
    tel = telemetry
    cfg = SYSTEMS["frontier"].cooling
    out = cal.calibrate(cfg, tel["p_it_w"], float(tel["dt"]),
                        tel["t_wetbulb_c"], _obs(tel))
    assert set(out.params) == set(RECOVERY_RTOL)
    for name, rtol in RECOVERY_RTOL.items():
        truth = float(tel[f"true_{name}"])
        got = out.params[name]
        err = abs(got - truth) / truth
        assert err <= rtol, (f"{name}: fitted {got:.6g} vs truth "
                             f"{truth:.6g} — {err:.2%} > {rtol:.0%}")
    # the fit must actually move: truth differs from the config defaults
    for name in ("ua_w_k", "tau_hx_s"):
        assert abs(out.params[name] - float(getattr(cfg, name))) > \
            0.05 * float(getattr(cfg, name))


def test_committed_envelope_holds(telemetry, fitted):
    """THE regression gate: committed params still reproduce the
    committed telemetry within the committed envelope * 5% slack."""
    tel = telemetry
    cfg = SYSTEMS["frontier"].cooling
    fresh = cal.check_envelope(fitted, cfg, tel["p_it_w"],
                               float(tel["dt"]), tel["t_wetbulb_c"],
                               _obs(tel))
    assert set(fresh) == set(fitted.envelope)


def test_envelope_gate_trips_on_degraded_physics(telemetry, fitted):
    """A plant that drifted from the calibration must fail the gate —
    proves the check has teeth, not just a vacuous pass."""
    import dataclasses
    tel = telemetry
    broken = dataclasses.replace(fitted,
                                 params={**fitted.params,
                                         "ua_w_k":
                                         fitted.params["ua_w_k"] * 2.0})
    with pytest.raises(TraceError, match="envelope widened"):
        cal.check_envelope(broken, SYSTEMS["frontier"].cooling,
                           tel["p_it_w"], float(tel["dt"]),
                           tel["t_wetbulb_c"], _obs(tel))


def test_fitted_params_json_is_self_describing(fitted):
    blob = json.loads((CAL_DIR / "fitted_params.json").read_text())
    assert blob["params"] == fitted.params
    assert fitted.meta["system"] == "frontier"
    assert sorted(fitted.meta["fit"]) == sorted(RECOVERY_RTOL)
    assert fitted.meta["channels"] == ["pue", "t_basin_c", "t_return_c",
                                      "t_supply_c"]
    for ch, v in fitted.envelope.items():
        assert np.isfinite(v) and v > 0.0, \
            f"{ch}: a zero/non-finite envelope makes the gate degenerate"


def test_simulate_plant_overrides_change_the_rollout(telemetry):
    tel = telemetry
    cfg = SYSTEMS["frontier"].cooling
    S = 500
    heat, wb = tel["p_it_w"][:S], tel["t_wetbulb_c"][:S]
    base = cal.simulate_plant(cfg, heat, float(tel["dt"]), wb)
    warm = cal.simulate_plant(cfg, heat, float(tel["dt"]), wb,
                              overrides={"ua_w_k": cfg.ua_w_k * 0.5})
    assert not np.array_equal(base["t_supply_c"], warm["t_supply_c"])
    for sim in (base, warm):
        for ch, v in sim.items():
            assert np.isfinite(v).all(), ch


def test_calibrate_rejects_mismatched_traces(telemetry):
    tel = telemetry
    cfg = SYSTEMS["frontier"].cooling
    with pytest.raises(TraceError):
        cal.calibrate(cfg, tel["p_it_w"][:100], float(tel["dt"]),
                      tel["t_wetbulb_c"], _obs(tel))
    with pytest.raises(TraceError):
        cal.calibrate(cfg, tel["p_it_w"], float(tel["dt"]),
                      tel["t_wetbulb_c"], {})
    with pytest.raises(TraceError):
        cal.calibrate(cfg, tel["p_it_w"], float(tel["dt"]),
                      tel["t_wetbulb_c"], _obs(tel), fit=("not_a_field",))


def test_calibrate_cli_check_gate(capsys):
    rc = cal.main(["--telemetry", str(CAL_DIR / "telemetry.npz"),
                   "--system", "frontier",
                   "--check", str(CAL_DIR / "fitted_params.json")])
    assert rc == 0
    assert "envelope holds" in capsys.readouterr().out


def test_calibrate_cli_check_exit_code_on_widened_envelope(tmp_path):
    """``simulate calibrate --check`` exits with calibrate's own return
    code: a committed envelope far tighter than the physics can meet
    (the fresh envelope has widened past it) fails the process."""
    import os
    import pathlib
    import subprocess
    import sys
    blob = json.loads((CAL_DIR / "fitted_params.json").read_text())
    blob["envelope"] = {ch: v * 1e-3 for ch, v in blob["envelope"].items()}
    tight = tmp_path / "tight_params.json"
    tight.write_text(json.dumps(blob))
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join(
                   [str(root / "src")] +
                   [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.simulate", "calibrate",
         "--telemetry", str(CAL_DIR / "telemetry.npz"),
         "--system", "frontier", "--check", str(tight)],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 1, out.stderr[-2000:]
    assert "envelope widened" in out.stdout
