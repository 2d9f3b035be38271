"""The check's verdict where nothing finished."""
import check


def test_nothing_finished_fails_the_check(cell_factory):
    v = check.check(cell_factory(), None, [], 1)
    assert not v.correct
    assert [n for n, _, _ in v.readings] == [
        "unfinished_requests", "wrong_length_requests", "served_logit_gap"]
    assert v.readings[2][1] > v.readings[2][2]
    assert (v.checked_requests, v.checked_tokens) == (0, 0)
