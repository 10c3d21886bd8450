"""The on-chip smoke script, rehearsed on the CPU at a tiny size.

The backend is steered to report TPU so the dispatcher plans the Pallas
kernels, and every kernel runs in Pallas' TPU interpret mode — the same
paths ``chip_smoke.py`` drives on the chip, minus the chip.
"""
import importlib.util
import pathlib
import sys

import jax
import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


TINY = dict(graph_nodes=256, kernel_n=256, serve_requests=6,
            serve_nodes=(48, 160))


def test_chip_smoke_phases_pass_in_tpu_interpret_mode(smoke, monkeypatch,
                                                       capsys):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        failures = smoke.run_phases(smoke.Sizes(**TINY))
    out = capsys.readouterr().out
    assert failures == [], out
    phases = [line for line in out.splitlines() if line.startswith("phase ")]
    assert len(phases) == 8, out
    assert all(": ok " in line for line in phases), out
    for line in phases:
        if "/ell" in line or "/sell" in line:
            assert "use_kernel=False" not in line, line


def test_chip_smoke_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    captured = capsys.readouterr()
    assert "platform=cpu" in captured.out
    assert '"ok"' not in captured.out
