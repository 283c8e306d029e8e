"""The traffic generators are a pure function of the seed, and every seed
gets the same amount of work."""

import json
from collections import Counter
from pathlib import Path

import numpy as np

from traffic import requests

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG = 2 ** 31 + 12345  # the driver's seeds are large


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _stream(mix, seed, n):
    t = requests.make(mix, seed, 64)
    return [t.request(i) for i in range(n)]


def test_same_seed_same_requests():
    for name in ("decode", "serve"):
        mix = _mix(name)
        assert _stream(mix, BIG, 200) == _stream(mix, BIG, 200)
        assert _stream(mix, BIG, 50) != _stream(mix, BIG + 1, 50)


def test_every_seed_gets_the_same_work():
    for name in ("decode", "serve"):
        mix = _mix(name)
        k = mix["block"]
        for part in (lambda p, n: len(p), lambda p, n: n):
            sizes = [Counter(part(p, n) for p, n, _ in _stream(mix, s, 2 * k))
                     for s in (1, BIG, 7 ** 12)]
            assert sizes[0] == sizes[1] == sizes[2]


def test_lengths_follow_the_mix():
    mix = _mix("decode")
    for p, n, seed in _stream(mix, BIG, 256):
        assert 16 <= len(p) <= 64 and 128 <= n <= 256
        assert 0 <= seed < 2 ** 32 and all(0 <= x < 64 for x in p)


def test_shared_prefixes():
    mix = _mix("serve")
    t = requests.make(mix, BIG, 64)
    reqs = [t.request(i) for i in range(mix["block"])]
    shared = [p for p, _, _ in reqs
              if any(p[:len(x)] == x for x in t.prefixes)]
    assert len(shared) == round(mix["shared"]["frac"] * mix["block"])
    for p, n, _ in reqs:
        assert len(p) + n <= 512  # fits the served cache


def test_open_loop_schedule():
    mix = _mix("serve")
    t = requests.make(mix, BIG, 64)
    due = t.due_times(3 * mix["block"])
    assert due[0] == 0 and np.all(np.diff(due) >= 0)
    assert np.array_equal(due, requests.make(mix, BIG, 64).due_times(3 * mix["block"]))
    gaps = np.diff(due[: mix["block"] + 1])
    rate = mix["arrivals"]["rate_rps"]
    # each block's gaps are the same sample rescaled to the mean gap
    whole = np.diff(t.due_times(mix["block"] + 1))
    assert abs(whole.mean() - 1 / rate) < 1e-9 * (1 / rate) + 1e-12
    assert gaps.std() / gaps.mean() > 1.2  # bursty (CV 2 by construction)


def test_every_seed_sends_the_same_sizes_at_the_same_times():
    """The seed picks the tokens; sizes, shares and arrivals are one fixed
    schedule."""
    for name in ("decode", "serve"):
        mix = _mix(name)
        a, b = requests.make(mix, BIG, 64), requests.make(mix, 7 ** 12, 64)
        n = 2 * mix["block"]
        ra = [a.request(i) for i in range(n)]
        rb = [b.request(i) for i in range(n)]
        assert [(len(p), m) for p, m, _ in ra] == [(len(p), m) for p, m, _ in rb]
        assert [p for p, _, _ in ra] != [p for p, _, _ in rb]
        if not a.closed:
            assert np.array_equal(a.due_times(n), b.due_times(n))
