import pytest

from bench import common


def test_percentile_is_nearest_rank():
    xs = list(range(1, 21))  # 1..20
    assert common.percentile(xs, 0.95) == 20  # index int(0.95 * 20) = 19
    assert common.percentile(xs, 0.5) == 11
    assert common.percentile([5.0], 0.95) == 5.0
    assert common.percentile(reversed(xs), 0.0) == 1
    with pytest.raises(ValueError):
        common.percentile([], 0.5)


class _Req:
    def __init__(self, reason="max_new"):
        self.reason = reason


class _Tracked:
    def __init__(self, t_submit, times, t_done=None, reason="max_new"):
        self.t_submit, self.times, self.t_done = t_submit, times, t_done
        self.req = _Req(reason)


class _Loop:
    def __init__(self, steps, done, live):
        self.steps, self.done, self.live = steps, done, live


def test_the_window_counts_only_what_lands_inside_it():
    from bench.cells.serve import window_stats

    steps = [{"t1": t} for t in (1.0, 2.0, 3.0, 4.0, 5.0)]
    a = _Tracked(0.5, [1.0, 2.0, 3.0, 3.0, 4.0], t_done=4.0)  # first token before the window
    b = _Tracked(2.5, [3.0, 5.0])  # first token inside; its last gap ends after the close
    c = _Tracked(3.5, [4.0], t_done=4.0, reason="deadline")
    ws = window_stats(_Loop(steps, [a, c], [b]), 2.0, 4.0)
    assert [s["t1"] for s in ws["steps"]] == [3.0, 4.0]  # (open, close]
    assert sorted(ws["gaps_ms"]) == [0.0, 1000.0]  # 3->3 and 3->4; 2->3 starts at the open
    assert sorted(ws["ttft_ms"]) == [500.0, 500.0]  # b: 2.5 -> 3.0, c: 3.5 -> 4.0
    assert ws["ended"] == [a, c] and ws["failed"] == [c]
