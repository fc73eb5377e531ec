"""A run whose timed path is broken underneath comes out not correct.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_broken_path.py -q

Drives ``run.run_cell`` at toy shapes on the CPU platform (the harness's
look for a chip is the one thing skipped) with the program sabotaged from
outside: a train step that returns its state unchanged, a served flow
altered where it is fetched. Sound runs of the same cells pass
(``rehearse.py``); these must not.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
pytest.importorskip("jax")


from benchmark.tests.toy_cell import toy_cell as _toy  # noqa: E402


def test_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, tmp_path, capsys):
    from benchmark import run as bench_run
    from raft_meets_dicl_tpu.strategy import training

    real = training.make_train_step

    def lazy(*args, **kwargs):
        step = real(*args, **kwargs)

        def unchanged(state, *rest):
            _, aux = step(state, *rest)
            return state, aux

        unchanged.stats = step.stats
        return unchanged

    def no_donation(*args, **kwargs):
        kwargs["donate"] = False
        return lazy(*args, **kwargs)

    monkeypatch.setattr(training, "make_train_step", no_donation)
    result = bench_run.run_cell(_toy("train"), 5, 2.0, 0, tmp_path,
                                platform="cpu")
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert "param_change_gap" in out and "FAILED" in out


def test_served_flow_altered_where_it_is_fetched_is_not_correct(
        monkeypatch, tmp_path, capsys):
    from benchmark import run as bench_run
    from raft_meets_dicl_tpu.serve import session

    real = session.ServeSession.fetch
    monkeypatch.setattr(session.ServeSession, "fetch",
                        lambda self, flow: real(self, flow) * 1.25)
    result = bench_run.run_cell(_toy("serve"), 5, 4.0, 0, tmp_path,
                                platform="cpu")
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert "serve_flow_gap" in out and "FAILED" in out
