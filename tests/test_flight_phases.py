"""Tier-1 gate for scripts/check_flight_phases.py: the declared flight
phase vocabulary (obs/flight.py PHASES) stays in lockstep with the
literal note_phase() call sites — statements_summary's avg_* columns,
the slow-log `# Phases` line and tidbtpu_flight_phase_seconds{phase}
all key on these names."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "scripts", "check_flight_phases.py")


def test_repo_is_clean():
    proc = subprocess.run(
        [sys.executable, LINT, REPO], capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, (
        f"flight-phase violations:\n{proc.stdout}{proc.stderr}"
    )


def test_lint_catches_violations(tmp_path):
    obs = tmp_path / "tidb_tpu" / "obs"
    obs.mkdir(parents=True)
    (obs / "flight.py").write_text(
        'PHASES = (\n    "parse",\n    "dead-phase",\n)\n'
        'FLIGHT = None\n'
    )
    (tmp_path / "tidb_tpu" / "engine.py").write_text(
        'from tidb_tpu.obs.flight import FLIGHT\n'
        'FLIGHT.note_phase("parse", 0.1)\n'
        'FLIGHT.note_phase("typo-phase", 0.1)\n'
    )
    proc = subprocess.run(
        [sys.executable, LINT, str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "typo-phase" in proc.stdout     # undeclared call site
    assert "dead-phase" in proc.stdout     # declared but never charged
    assert "'parse'" not in proc.stdout    # declared + used: clean


def test_runtime_rejects_undeclared_phase():
    """note_phase is the runtime half of the lint: an undeclared name
    raises instead of silently forking the breakdown."""
    from tidb_tpu.obs.flight import FlightRecorder

    f = FlightRecorder()
    f.begin("select 1")
    with pytest.raises(ValueError, match="undeclared flight phase"):
        f.note_phase("no-such-phase", 0.1)
    f.discard()


def test_lint_holds_span_sites_to_the_same_rules(tmp_path):
    """FLIGHT.span("name") sites count as charging the phase of that
    name, and SPANS is checked like PHASES: undeclared sites and dead
    declarations are both violations."""
    obs = tmp_path / "tidb_tpu" / "obs"
    obs.mkdir(parents=True)
    (obs / "flight.py").write_text(
        'PHASES = (\n    "plan",\n)\n'
        'SPANS = (\n    "plan",\n    "inputs",\n    "dead-span",\n)\n'
        'FLIGHT = None\n'
    )
    (tmp_path / "tidb_tpu" / "engine.py").write_text(
        'from tidb_tpu.obs.flight import FLIGHT as _FLIGHT\n'
        'with _FLIGHT.span("plan"):\n    pass\n'
        'with _FLIGHT.span("inputs"):\n    pass\n'
        'with _FLIGHT.span("typo-span"):\n    pass\n'
    )
    proc = subprocess.run(
        [sys.executable, LINT, str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    assert "typo-span" in proc.stdout      # undeclared span site
    assert "dead-span" in proc.stdout      # declared, never opened
    assert "'plan'" not in proc.stdout     # the span site charges the phase
    assert "'inputs'" not in proc.stdout
