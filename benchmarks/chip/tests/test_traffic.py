"""The traffic generators: a kind is found by its file's name, the open
loop's arrivals are Poisson given their count, and every seed's window
holds the same sizes."""
import json

import numpy as np
import pytest

import traffic

CHAT = json.loads((traffic.KINDS / "chat-poisson.json").read_text())
SHORT = json.loads((traffic.KINDS / "short-closed.json").read_text())


def drain(mix, until):
    mix.start(0.0)
    return mix.take(until)


def test_unknown_kind_names_the_missing_file():
    with pytest.raises(ValueError, match="nope.py"):
        traffic.load({"kind": "nope"}, 1, 1.0, 1.0, 100)


def test_a_new_kind_is_a_new_file(tmp_path, monkeypatch):
    (tmp_path / "every.py").write_text(
        "import traffic\n"
        "class Mix(traffic.Mix):\n"
        "    def take(self, now):\n"
        "        return [self.make(now, 3, 2)] if self.n == 0 else []\n")
    monkeypatch.setattr(traffic, "KINDS", tmp_path)
    mix = traffic.load({"kind": "every"}, 5, 1.0, 1.0, 100)
    (rid, due, prompt, max_new), = drain(mix, 0.5)
    assert (rid, len(prompt), max_new) == (0, 3, 2)


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 9])
def test_poisson_window_holds_the_same_sizes_at_other_times(seed):
    ramp, window = CHAT["ramp_s"], 50.0
    a = drain(traffic.load(CHAT, seed, ramp, window, 1000), 1e9)
    b = drain(traffic.load(CHAT, seed + 1, ramp, window, 1000), 1e9)
    n = round(CHAT["rate_per_s"] * window)
    win = [[r for r in x if ramp <= r[1] < ramp + window] for x in (a, b)]
    assert [len(w) for w in win] == [n, n]
    for key in (lambda r: len(r[2]), lambda r: r[3]):
        assert sorted(map(key, win[0])) == sorted(map(key, win[1]))
    assert [r[1] for r in win[0]] != [r[1] for r in win[1]]
    tail = [r for r in a if r[1] >= ramp + window]
    assert len(tail) == n and max(r[1] for r in tail) < ramp + 2 * window


def test_poisson_gaps_are_exponential():
    """Uniform arrival times given their count: the gaps' spread equals
    their mean, as an exponential's does (a fixed set of gaps would not
    cluster)."""
    spec = dict(CHAT, rate_per_s=10.0)
    due = np.array([r[1] for r in drain(
        traffic.load(spec, 3, 0.0, 2000.0, 10), 2000.0)])
    gaps = np.diff(due)
    assert abs(gaps.mean() - 0.1) < 0.005
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05


def test_closed_loop_sends_on_completion_in_rounds_of_quantiles():
    mix = traffic.load(SHORT, 7, SHORT["ramp_s"], 10.0, 1000)
    c, k = SHORT["clients"], SHORT["round"]
    first = drain(mix, SHORT["start_spread_s"])
    assert len(first) == c
    grid = traffic.quantiles(SHORT["output"], k).tolist()
    for i in range(0, c, k):
        assert sorted(r[3] for r in first[i:i + k]) == sorted(grid)
    assert mix.take(1e9) == []
    mix.done(first[5][0], 100.0)
    assert mix.next_due() == 100.0
    (nxt,) = mix.take(100.0)
    assert nxt[0] == c
