"""The control and the faults come out as NOT correct.

Drives the harness as a run does — generator child, cluster, window, checks,
result — minus its look for a chip, tiny, on the CPU (`rehearse.py`), with
the timed path broken underneath (`faults.py`).  Of the faults a cell can
have, these exist here: an answer altered where it is produced, and (the
control) the code the configuration states computed a shard short.  There
is no training step, no batch mean and no exchange between chips in a
one-chip storage cell.

Slow (a cluster boot each, ~40 s): `python -m pytest benchmark/tests/test_control.py`.
"""

import pytest

import faults
import node_loss
import rehearse

CASES = [
    ("ec83-put-8m", "control_parity_shard_dropped", "pieces_wrong", 4.0),
    ("ec83-put-8m", "fault_pieces_acknowledged_and_dropped", "blocks_under_quorum", 4.0),
    ("ec42-small-mixed", "control_parity_shard_dropped", "pieces_wrong", 4.0),
    ("ec42-small-mixed", "fault_answer_altered", "answers_wrong", 4.0),
    ("ec83-node-loss", "fault_rebuilt_piece_altered", "restored_pieces_wrong", 15.0),
]


@pytest.mark.parametrize("cell,fault,number,seconds", CASES)
def test_broken_underneath_is_not_correct(cell, fault, number, seconds):
    try:
        r = rehearse.rehearse(cell, traced=False, seconds=seconds, sabotage=faults.BY_NAME[fault],
                              bench=node_loss.bench_with_node_loss())
    finally:
        faults.undo()
    assert r["correct"] is False
    assert not r["checks"][number]["ok"], r["checks"]
    assert r["checks"][number]["value"] > r["checks"][number]["limit"]


def test_refused_requests_are_failed_not_wrong():
    """A 5xx says the request was not served, which is true: it counts in
    `failed` (and so in the tails and the goodput), the preload and the
    read-back ask again, and `correct` stays for what was answered and stored."""
    try:
        r = rehearse.rehearse("ec42-small-mixed", traced=True, seconds=4.0,
                              sabotage=faults.BY_NAME["requests_refused"])
    finally:
        faults.undo()
    assert r["correct"] is True, r["checks"]
    assert r["failed"] > 0 and r["refused"]["preload_and_traffic"] >= r["failed"]
    assert r["refused"]["readback_asked_again"] > 0
    assert r["metrics"]["requests_failed_per_kop"]["value"] == pytest.approx(
        1000.0 * r["failed"] / r["attempted"])


def test_outcome_of_a_request():
    from harness.loadgen import outcome

    assert outcome("") == "ok"
    assert outcome("500 PUT k: b'could not reach quorum'") == "refused"
    assert outcome("503 GET k: b'SlowDown'") == "refused"
    assert outcome("client error on GET k: ConnectionResetError()") == "refused"
    assert outcome("no answer in 60.0 s to GET k") == "unanswered"
    assert outcome("404 GET k: b'NoSuchKey'") == "wrong"
    assert outcome("403 PUT k: b'AccessDenied'") == "wrong"
    assert outcome("WRONG BYTES from GET k (65536 returned)") == "wrong"
    assert outcome("WRONG LENGTH from HEAD k: 5") == "wrong"


@pytest.mark.parametrize("cell,seconds", [("ec83-put-8m", 4.0), (node_loss.NAME, 15.0)])
def test_sound_run_is_correct(cell, seconds):
    r = rehearse.rehearse(cell, traced=True, seconds=seconds, bench=node_loss.bench_with_node_loss())
    assert r["correct"] is True, r["checks"]
    assert all(c["ok"] for c in r["checks"].values())
    if cell == node_loss.NAME:
        assert r["checks"]["stripes_restored"]["value"] > 0
        assert {"repair_host_decode_pct", "repair_blocks_per_round"} <= set(r["metrics"])
