"""Each cell rehearsed on the CPU at a tiny size, and the runs that must
come out not correct: the control and the faults a cell can have."""
import pytest

from bench import harness, run as bench_run
from bench.drivers import train
from bench.tests.rehearse import run_tiny, steered, tiny_inputs

TRAIN_CELLS = ["gcn-train-pl16k", "gat-train-pl16k"]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_cell_rehearses_correct_with_the_contract_keys(workload, capsys,
                                                       monkeypatch):
    with steered(monkeypatch):
        result, out = run_tiny(workload, capsys)
    assert list(result) == RESULT_KEYS + ["checks"], out.out
    assert result["correct"] is True, out.err
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "setup_s" in result["metrics"]
    assert len(result["metrics"]) == 2
    assert "compiles in window: 0;" in out.out
    # the numbers compared are the last lines of stderr
    last = out.err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in last)


def test_traced_run_has_the_trace_keys(capsys, monkeypatch):
    with steered(monkeypatch):
        result, out = run_tiny("gcn-train-pl16k", capsys, trace=True)
    assert list(result) == RESULT_KEYS + ["breakdown", "checks"]
    assert result["correct"] is True, out.err
    assert {"busy_s", "window_s"} <= set(result["device"])
    # the CPU's trace has no device plane: every reader finds nothing,
    # and a metric that finds nothing is left out, never reported as 0
    assert result["metrics"] == {}


def test_no_chip_exits_nonzero_without_a_result(capsys):
    assert bench_run.main(["--workload", "gcn-train-pl16k", "--seed", "1",
                           "--seconds", "1"]) == 3
    out = capsys.readouterr()
    assert "{" not in out.out
    assert "no TPU" in out.err


@pytest.mark.parametrize("workload", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(workload, fault, capsys, monkeypatch):
    if fault == "state_unchanged":
        monkeypatch.setattr(train, "sgd", lambda params, grads, lr: params)
    else:
        nll = train.nll
        monkeypatch.setattr(
            train, "nll", lambda lg, lb: nll(lg[:lg.shape[0] // 2],
                                             lb[:lb.shape[0] // 2]))
    with steered(monkeypatch):
        result, out = run_tiny(workload, capsys)
    assert result["correct"] is False, out.err


@pytest.mark.parametrize("workload", TRAIN_CELLS)
def test_train_control_is_not_correct(workload, monkeypatch):
    _, cell, config, traffic = tiny_inputs(workload)
    with steered(monkeypatch):
        inputs = train.make_graph(config)
        got = train.control_readings(config, traffic, 5, inputs)
    ok, _ = harness.judge(got, harness.limits(workload))
    assert not ok, got
