"""``correct`` comes out true for the program as it is, and false for
the control (the reference in bfloat16) and for each fault the timed
path can have, planted underneath a whole run at a small size on the
CPU: an answer altered where it is produced, half of the answers left
out, and answers handed to the wrong requests."""

import json

import pytest

from benchmark import run
from benchmark.control import control_numbers

DOCS = 1 << 12
CELLS = ["msmarco.bm25.serial", "sift1m.knn.open"]


def run_cell(workload, capsys, seed=2 ** 31 + 77, seconds=2.0):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_tpu=False, n_docs=DOCS)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(workload, capsys):
    result = run_cell(workload, capsys)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit(workload):
    got = control_numbers(workload, 5, n_docs=DOCS, check=200)
    assert any(v["value"] > v["limit"] for v in got.values()), got


def _alter_fetch(monkeypatch):
    from elasticsearch_tpu.action import search_action
    orig = search_action.fetch_hits

    def fetch_hits(*args, **kwargs):
        hits = orig(*args, **kwargs)
        for hit in hits[:1]:
            hit["_id"] = str((int(hit["_id"]) + 1) % DOCS)
        return hits
    monkeypatch.setattr(search_action, "fetch_hits", fetch_hits)


def _drop_half(monkeypatch):
    from elasticsearch_tpu.search import batch_executor
    orig = batch_executor.ShardQueryBatcher._deliver
    seen = [0]

    def deliver(self, members):
        for m in members:
            seen[0] += 1
            if seen[0] % 2:
                m.error = RuntimeError("left out")
        return orig(self, members)
    monkeypatch.setattr(batch_executor.ShardQueryBatcher, "_deliver",
                        deliver)


def _swap_answers(monkeypatch):
    from elasticsearch_tpu.search import batch_executor
    orig = batch_executor.ShardQueryBatcher._deliver
    carry = [None]

    def deliver(self, members):
        for m in members:
            if m.error is None and carry[0] is not None:
                m.result, carry[0] = carry[0], m.result
            elif m.error is None:
                carry[0] = m.result
        return orig(self, members)
    monkeypatch.setattr(batch_executor.ShardQueryBatcher, "_deliver",
                        deliver)


FAULTS = {"altered_answer": _alter_fetch, "half_left_out": _drop_half,
          "answers_swapped": _swap_answers}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch, capsys):
    from benchmark import harness
    warm_up = harness.warm_up

    def warm_then_break(*args, **kwargs):
        out = warm_up(*args, **kwargs)
        FAULTS[fault](monkeypatch)      # planted as the window opens
        return out
    monkeypatch.setattr(harness, "warm_up", warm_then_break)
    result = run_cell(workload, capsys)
    assert not result["correct"], result["compared"]
