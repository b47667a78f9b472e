"""A run with the timed path broken underneath reads ``correct`` false:
the harness's look for a chip is skipped and everything else of a run
is driven on the CPU at a tiny shape, with the service's answers
broken between where they are produced and the client, once for each
fault a cell of this benchmark can have.  One chip per cell, so no
exchange between chips to leave out; no training state to leave
unchanged."""
from __future__ import annotations

from concurrent.futures import Future

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.rehearsal import BENCH, CELLS, tiny


def altered(answer):
    """One answer changed where it is produced: a container byte, or one
    decoded value moved to its neighbouring float."""
    if isinstance(answer, bytes):
        return answer[:-1] + bytes([answer[-1] ^ 1])
    out = np.array(answer)
    out.flat[out.size // 2] = np.nextafter(out.flat[out.size // 2],
                                           np.float32(np.inf))
    return out


def _relay(fut: Future, fn) -> Future:
    out: Future = Future()

    def done(f):
        try:
            out.set_result(fn(f.result()))
        except Exception as e:  # noqa: BLE001 - the client sees it
            out.set_exception(e)

    fut.add_done_callback(done)
    return out


def alter_every_answer(issue):
    def broken(state, i):
        fut, nbytes = issue(state, i)
        return _relay(fut, altered), nbytes
    return broken


def cross_answers(issue):
    """Each request gets the answer of the one before it: answers of a
    batch land on the wrong requests, as when part of a batch is left
    out and the rest fills its places."""
    futures: dict[int, Future] = {}

    def broken(state, i):
        fut, nbytes = issue(state, i)
        futures[i] = fut
        other = futures.get(i - 1)
        return (_relay(other, lambda a: a) if other is not None else fut,
                nbytes)
    return broken


@pytest.mark.parametrize("fault", [alter_every_answer, cross_answers])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    cfg = tiny(cell)
    traffic = harness.load_json(
        "traffic", harness.cell_of(BENCH, cell)["traffic"])
    op = harness.load_module("ops", traffic["op"])
    monkeypatch.setattr(op, "issue", fault(op.issue))
    res = harness.run_cell(cell, 11, 1, False, bench=BENCH, cfg=cfg,
                           peaks=harness.peaks_for("TPU v5 lite"))
    assert not res["correct"], res["checks"]
