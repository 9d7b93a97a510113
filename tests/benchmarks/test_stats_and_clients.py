"""Percentiles, and the closed-loop client's numbers on a synthetic list of
token times."""
import numpy as np
import pytest

import bench_paths  # noqa: F401  (puts the benchmark on the path)
from harness import kind_serve_closed as k, stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys(q):
    xs = [0.3, 0.1, 0.9, 0.5, 0.7, 0.2, 0.8]
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_edges():
    assert stats.percentile([], 50) is None
    assert stats.percentile([4.0], 95) == 4.0
    assert stats.token_gaps([1.0, 1.5, 2.5]) == [0.5, 1.0]


def _req(index, t_submit, times, max_new=3):
    r = k.Request(index, 0, np.arange(4, dtype=np.int32), max_new)
    r.t_submit, r.times = t_submit, list(times)
    r.tokens = list(range(len(times)))
    return r


def test_measure_counts_by_delivery_time():
    reqs = [
        _req(0, 9.0, [9.5, 10.2, 10.4]),      # submitted before the window
        _req(1, 10.1, [10.3, 10.6, 10.9]),    # wholly inside
        _req(2, 10.8, [11.2, 11.4]),          # first token after the close
        _req(3, 10.9, []),                    # never answered
    ]
    m = k.measure(reqs, 10.0, 11.0)
    assert m["tokens"] == 2 + 3
    # gaps whose later token fell inside the window
    assert sorted(round(g, 6) for g in m["gaps"]) == [0.2, 0.3, 0.3, 0.7]
    assert m["attempted"] == 3 and m["failed"] == 1
    assert sorted(round(t, 6) for t in m["ttfts"]) == [0.2, 0.4]


def test_sample_keeps_the_longest_finished_request():
    reqs = [_req(i, 10.0, [10.1, 10.2, 10.3]) for i in range(6)]
    reqs[4].prompt = np.arange(40, dtype=np.int32)
    reqs.append(_req(6, 10.0, [10.1]))          # not finished
    for seed in (1, 2, 3):
        sample = k.pick_sample(reqs, 10.0, 11.0, seed, 3)
        assert len(sample) == 3 and sample[0] is reqs[4]
        assert all(r.finished for r in sample)
    assert k.pick_sample(reqs, 20.0, 21.0, 1, 3) == []


def test_plan_offers_the_same_lengths_on_every_seed():
    traffic = {"prompt_lens": [5, 9, 17, 12], "output_lens": [4, 6, 5, 3]}
    seen = []
    for seed in (1, 2**31 + 7):
        make = k.plan(traffic, seed, 100)
        reqs = [make(i) for i in range(4)]
        seen.append((sorted(len(p) for p, _ in reqs),
                     sorted(o for _, o in reqs)))
        assert all(0 <= p.min() and p.max() < 100 for p, _ in reqs)
    assert seen[0] == seen[1] == ([5, 9, 12, 17], [3, 4, 5, 6])
