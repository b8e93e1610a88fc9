"""chip_smoke.py's contract, as far as a machine without a chip can show
it: every phase's failure fails the run, no chip means no result line, and
the rehearsal switch can never print one. (What the phases check is proven
on the chip, by running the script there.)"""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402

ONE_CHIP = ["device", "train", "serve", "parity", "kernels"]


class _Meter:
    """Stands in for CompileMeter: no listener is left on this process."""
    seconds = requests = cache_hits = 0

    def snapshot(self):
        return (0.0, 0, 0)


@pytest.fixture
def stubbed(monkeypatch):
    """chip_smoke with every phase replaced by one that passes at once."""
    monkeypatch.setattr(chip_smoke, "CompileMeter", _Meter)

    def device(args, sz, meter, state):
        # a host that shows four chips, whatever --chips asks for
        state.update(platform="tpu", kind="stub", count=4)
        return {}
    monkeypatch.setattr(chip_smoke, "phase_device", device)
    for name in ONE_CHIP[1:] + ["multichip"]:
        monkeypatch.setattr(chip_smoke, "phase_" + name,
                            lambda args, sz, meter, state: {})
    return chip_smoke


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


@pytest.mark.parametrize("phase,argv", [(p, []) for p in ONE_CHIP]
                         + [("multichip", ["--chips", "4"])])
def test_injected_failure_in_any_phase_fails_the_run(stubbed, monkeypatch,
                                                     capsys, phase, argv):
    def broken(args, sz, meter, state):
        raise RuntimeError(f"injected failure in phase {phase}")
    monkeypatch.setattr(stubbed, "phase_" + phase, broken)
    with pytest.raises(RuntimeError, match=f"phase {phase}"):
        stubbed.main(argv)
    lines = _lines(capsys)
    assert lines[-1]["phase"] == phase and lines[-1]["ok"] is False
    assert not any("device" in ln and ln.get("ok") is True for ln in lines
                   if "phase" not in ln), "a result line was printed"


@pytest.mark.parametrize("argv,phases,count", [
    ([], ONE_CHIP, 1),              # the chips the run used, not the host's
    (["--chips", "4"], ["device", "multichip"], 4),   # no one-chip phase
])
def test_passing_run_ends_in_the_result_line(stubbed, capsys, argv, phases,
                                             count):
    assert stubbed.main(argv) == 0
    lines = _lines(capsys)
    assert [ln["phase"] for ln in lines[:-1]] == phases
    assert lines[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "stub", "count": count}}


def test_rehearsal_never_prints_the_result_line(stubbed, capsys):
    assert stubbed.main(["--rehearse"]) == 2
    last = _lines(capsys)[-1]
    assert last["ok"] is False and last["rehearsal"] is True


def test_without_a_chip_the_script_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
