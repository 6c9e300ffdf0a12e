"""``correct`` comes out true for the program as it is, and false for
the control (the reference in bfloat16) and for each fault the timed
path can have, planted underneath a whole run at a small size on the
CPU: an answer altered where it is produced, half of the answers left
out, and answers handed to the wrong requests; in the four-shard cell
also the other chips' shard results left out of the merge, and scoring
with index-wide statistics where ``query_then_fetch`` scores each shard
with its own."""

import json
from pathlib import Path

import pytest

from benchmark import run
from benchmark.control import control_numbers

DOCS = 1 << 12
MESH_CELL = "msmarco.bm25.mesh4.open"
CELLS = ["msmarco.bm25.serial", "sift1m.knn.open", MESH_CELL]


HELD = json.loads((Path(__file__).parent / "data" / "held_mesh4.json")
                  .read_text())


@pytest.fixture(autouse=True)
def _held_mesh_cell(monkeypatch):
    """The four-shard cell is held out of BENCHMARK.json until its rate
    and limits are read on a four-chip host; its entries are kept in
    ``data/held_mesh4.json``, and runs of it here read them."""
    orig = run.cell_spec

    def cell_spec(workload):
        if workload != MESH_CELL:
            return orig(workload)
        bench = orig("msmarco.bm25.serial")[0]
        cell = HELD["workload"]
        cfg = json.loads((run.ROOT / HELD["config"]["file"]).read_text())
        traffic = json.loads((run.HERE / "traffic" /
                              f"{cell['traffic']}.json").read_text())
        metrics = {"end_to_end": [m for m in bench["end_to_end"]
                                  if "workloads" not in m],
                   "per_layer": HELD["per_layer"]}
        return bench, cell, cfg, traffic, metrics
    monkeypatch.setattr(run, "cell_spec", cell_spec)


def run_cell(workload, capsys, seed=2 ** 31 + 77, seconds=2.0, trace=0):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
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


def test_mesh_serves_every_request_of_the_window(capsys):
    """The four-shard cell measures the mesh program, not the RPC
    fan-out it falls back to: every request of the window is served
    from the mesh, none falls back."""
    result = run_cell(MESH_CELL, capsys, trace=1)
    assert result["correct"], result["compared"]
    assert result["metrics"]["mesh_served_pct"]["value"] == 100.0
    assert result["metrics"]["mesh_occupancy.open"]["value"] >= 1.0
    assert "mesh_rest_ms" in result["metrics"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit(workload):
    got = control_numbers(workload, 5, n_docs=DOCS, check=200)["control"]
    assert any(v["value"] > v["limit"] for v in got.values()), got


def test_index_wide_answers_fail_a_limit():
    """The reference with index-wide statistics, put in the program's
    place, fails a limit: the reference holds the four-shard cell to
    ``query_then_fetch``'s per-shard statistics."""
    got = control_numbers(MESH_CELL, 5, n_docs=DOCS, check=200,
                          answers=("index_wide",))["index_wide"]
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


def _emptied(shard_results):
    """Per-shard query results that found nothing."""
    return [dict(r, docs=[], total=0, max_score=None)
            for r in shard_results]


def _patch_mesh(monkeypatch, alter):
    """The mesh executor hands each search's per-shard results, as
    ``alter`` leaves them, to the coordinator's merge."""
    from elasticsearch_tpu.search import mesh_executor
    orig = mesh_executor.MeshSearchExecutor._deliver

    def deliver(self, member, res):
        return orig(self, member, res if res is None else alter(res))
    monkeypatch.setattr(mesh_executor.MeshSearchExecutor, "_deliver",
                        deliver)


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

    def mesh_half(res):
        seen[0] += 1
        return _emptied(res) if seen[0] % 2 else res
    _patch_mesh(monkeypatch, mesh_half)


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

    def mesh_swap(res):
        if carry[0] is None:
            carry[0] = res
            return res
        res, carry[0] = carry[0], res
        return res
    _patch_mesh(monkeypatch, mesh_swap)


def _drop_other_shards(monkeypatch):
    _patch_mesh(monkeypatch, lambda res: res[:1] + _emptied(res[1:]))


def _index_wide_stats(monkeypatch):
    from elasticsearch_tpu.action.search_action import TransportSearchAction
    orig = TransportSearchAction._execute_admitted

    def execute(self, index_expression, body, on_done,
                search_type="query_then_fetch"):
        return orig(self, index_expression, body, on_done,
                    "dfs_query_then_fetch")
    monkeypatch.setattr(TransportSearchAction, "_execute_admitted", execute)


FAULTS = {"altered_answer": _alter_fetch, "half_left_out": _drop_half,
          "answers_swapped": _swap_answers}
MESH_FAULTS = {"other_shards_left_out": _drop_other_shards,
               "index_wide_stats": _index_wide_stats}


def run_broken(workload, plant, monkeypatch, capsys):
    from benchmark import harness
    warm_up = harness.warm_up

    def warm_then_break(*args, **kwargs):
        out = warm_up(*args, **kwargs)
        plant(monkeypatch)              # planted as the window opens
        return out
    monkeypatch.setattr(harness, "warm_up", warm_then_break)
    return run_cell(workload, capsys)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch, capsys):
    result = run_broken(workload, FAULTS[fault], monkeypatch, capsys)
    assert not result["correct"], result["compared"]


@pytest.mark.parametrize("fault", sorted(MESH_FAULTS))
def test_mesh_fault_is_not_correct(fault, monkeypatch, capsys):
    result = run_broken(MESH_CELL, MESH_FAULTS[fault], monkeypatch, capsys)
    assert not result["correct"], result["compared"]
