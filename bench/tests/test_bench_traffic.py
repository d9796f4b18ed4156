"""The seeded request generator."""

import numpy as np

import _tiny  # noqa: F401  (puts the repo on sys.path)
from bench import traffic

OPEN = {"loop": "open", "arrivals": {"kind": "poisson", "rate_rps": 5.0},
        "stratum": 16,
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.7,
                   "lo": 128, "hi": 3584},
        "answer": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                   "lo": 16, "hi": 512}}
CLOSED = {"loop": "closed", "requests": 24, "stratum": 8,
          "prompt": {"dist": "uniform", "lo": 4097, "hi": 7680},
          "answer": {"dist": "lognormal", "median": 512, "sigma": 0.5,
                     "lo": 256, "hi": 1024}}


def _make(tr, seed, seconds=32.0, max_len=8192):
    return traffic.make_requests(tr, seed=seed, vocab=1000, max_len=max_len,
                                 seconds=seconds)


def _key(reqs):
    return [(r.id, r.prompt.tobytes(), r.max_new, r.due) for r in reqs]


def test_same_seed_same_requests():
    for tr in (OPEN, CLOSED):
        assert _key(_make(tr, 2**31 + 5)) == _key(_make(tr, 2**31 + 5))
        assert _key(_make(tr, 1)) != _key(_make(tr, 2))


def test_every_seed_gets_the_same_sizes_in_another_order():
    a, b = _make(CLOSED, 3), _make(CLOSED, 4)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # every stratum holds the quantile set
    for i in range(0, 24, 8):
        assert sorted(len(r.prompt) for r in a[i:i + 8]) == [
            4321, 4769, 5217, 5665, 6113, 6561, 7009, 7457]
    ga = np.diff([r.due for r in _make(OPEN, 3)])
    gb = np.diff([r.due for r in _make(OPEN, 4)])
    assert np.allclose(sorted(ga)[:40], sorted(gb)[:40])


def test_a_schedule_seed_fixes_sizes_and_order():
    tr = dict(CLOSED, schedule_seed=7)
    a, b = _make(tr, 3), _make(tr, 2**31 + 4)
    assert [(len(r.prompt), r.max_new) for r in a] == [
        (len(r.prompt), r.max_new) for r in b]
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes()
                                               for r in b]
    assert [len(r.prompt) for r in a] != [len(r.prompt)
                                          for r in _make(CLOSED, 3)]


def test_open_loop_due_times_fill_the_window_at_the_rate():
    reqs = _make(OPEN, 9, seconds=32.0)
    due = np.array([r.due for r in reqs])
    assert due[0] == 0.0 and np.all(np.diff(due) >= 0)
    assert due[-1] < 32.0
    assert 150 <= len(reqs) <= 160            # 5 req/s for 32 s
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= 128 and max(lens) <= 3584
    # stratified lognormal: median of the quantile set is the median
    assert abs(np.median(lens) - 1024) < 80


def test_answers_are_cut_to_fit_max_len():
    for r in _make(CLOSED, 5):
        assert len(r.prompt) + r.max_new <= 8192
        assert r.max_new >= 256 or len(r.prompt) + r.max_new == 8192


def test_gamma_gaps_keep_the_mean_rate():
    tr = dict(OPEN, arrivals={"kind": "gamma", "rate_rps": 8.0, "cv2": 4.0})
    gaps = traffic.gap_quantiles(tr["arrivals"], (np.arange(512) + .5) / 512)
    assert abs(np.mean(gaps) - 1 / 8.0) < 0.01
    assert np.var(gaps) / np.mean(gaps) ** 2 > 3.0
