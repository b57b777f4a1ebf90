"""A backlog cell whose queue ran out inside the window is not ``correct``, and
says that the CELL is too short: on hand-made steps, and through a whole
``--rehearse`` run (everything but the harness's look for a chip) of a cell
whose backlog is cut to fewer requests than its window consumes. The cells as
committed still rehearse, and a Poisson cell is not held to the guard."""

import json
import sys

import pytest

from perfbench import run as bench_run
from perfbench.kinds.serve_open_loop import backlog_guard


def steps(backlogs, t0=100.0, dt=0.02):
    """``(t_before, t_after, active, blocks, backlog, tokens)`` a step, as the kind records them."""
    return [(t0 + i * dt, t0 + (i + 1) * dt, 4, 10, b, 7 * i) for i, b in enumerate(backlogs)]


def test_a_queue_that_lasts_passes_with_its_least_depth():
    assert backlog_guard("sc2-3b.chat-saturated", 2400, steps([1900, 1850, 1801, 1803]), 100.0, 100.08) == (1801, None)
    assert backlog_guard("c", 5, steps([1]), 100.0, 100.02) == (1, None)


def test_a_queue_that_ran_out_names_the_cell_and_the_key():
    least, why = backlog_guard("sc2-3b.chat-saturated", 600, steps([3, 1, 0, 0, 0]), 100.0, 100.1)
    assert least == 0
    assert "the backlog of 600 requests ran out 0.0 s before the window closed" in why  # the third step ends at 100.06
    assert "sc2-3b.chat-saturated is too short for this program" in why and "benchmark PR extends backlog_total" in why
    least, why = backlog_guard("c", 600, steps([0] * 5), 100.0, 151.0)
    assert least == 0 and "ran out 51.0 s before" in why
    # No step at all in the window: the pool was idle from before it opened.
    least, why = backlog_guard("laguna-s.agent-saturated", 800, [], 100.0, 151.0)
    assert least == 0 and "ran out 51.0 s before" in why and "laguna-s.agent-saturated" in why


def rehearse(monkeypatch, capsys, workload, change=None):
    """``perfbench.run`` in this process at the cell's rehearsal size; ``change(cell)`` edits the cell first."""
    load = bench_run.load_json

    def load_changed(*parts):
        doc = load(*parts)
        if change is not None and parts[0] == "workloads":
            change(doc)
        return doc

    monkeypatch.setattr(bench_run, "load_json", load_changed)
    monkeypatch.setattr(sys, "argv", ["perfbench.run", "--workload", workload, "--seed", "3000000019", "--seconds", "4",
                                      "--rehearse"])
    rc = bench_run.main()
    out, err = capsys.readouterr()
    lines = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    notes = {x["note"]: x for x in lines if "note" in x}
    return rc, lines[-1], notes, err


def test_a_run_whose_backlog_is_too_short_is_not_correct(monkeypatch, capsys):
    def cut(cell):
        cell["traffic"].pop("backlog_total")
        cell["rehearse"]["traffic"]["backlog_requests"] = 40  # the tiny model consumes hundreds in its 3 + 4 s

    rc, result, notes, err = rehearse(monkeypatch, capsys, "sc2-3b.chat-saturated", cut)
    assert rc == 1 and result["correct"] is False and result["failed"] == 0
    assert notes["serve"]["offered"] == 40 and notes["serve"]["backlog_min_in_window"] == 0
    assert notes["serve"]["wrong_length"] == 0 and notes["check"]["ok"] is True  # nothing else is at fault
    assert "is not correct: the backlog of 40 requests ran out" in err
    assert "the cell sc2-3b.chat-saturated is too short for this program; a benchmark PR extends backlog_total" in err
    assert "perfbench: compared backlog_min_in_window = 0 (has to be >= 1)" in err.splitlines()


@pytest.mark.parametrize("workload,total", [("sc2-3b.chat-saturated", 2400), ("laguna-s.agent-saturated", 1600),
                                            ("sc2-3b.chat-steady", None)])
def test_the_serving_cells_still_rehearse(monkeypatch, capsys, workload, total):
    rc, result, notes, err = rehearse(monkeypatch, capsys, workload)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    compared = [x for x in err.splitlines() if x.startswith("perfbench: compared ")]
    assert err.splitlines()[-len(compared):] == compared  # the last lines of standard error
    if total is None:  # Poisson arrivals: no backlog to hold
        assert notes["serve"]["arrivals"] == "poisson" and notes["serve"]["backlog_min_in_window"] is None
        assert not any("backlog_min_in_window" in x for x in compared)
    else:
        assert notes["serve"]["offered"] == notes["serve"]["submitted"] == total
        assert notes["serve"]["backlog_min_in_window"] == notes["serve"]["backlog_at_close"] > 0
        assert any("backlog_min_in_window" in x for x in compared)
