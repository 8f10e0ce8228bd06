"""The traffic generator: the same seed gives the same inputs, and every
seed offers the same load."""

import json
import os

import numpy as np
import pytest

from bench.traffic import Mix

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "traffic")
SHAPE = (8, 8, 3)
REPLAY = {"arrivals": "backlog", "queued_per_slot": 2, "streams": 8,
          "repeat_share": 0.5}


def mix(name, seed):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return Mix(json.load(f), seed, SHAPE)


def reordered(seed, order_seed):
    """The poisson mix with its gaps in another order."""
    spec = dict(mix("poisson-vgg19", 0).spec, order_seed=order_seed)
    return Mix(spec, seed, SHAPE)


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 98765432101])
def test_poisson_gaps_are_one_multiset(seed):
    a = mix("poisson-vgg19", seed).due_times(51)
    b = reordered(seed, 2).due_times(51)
    assert len(a) == len(b) == round(mix("poisson-vgg19", 0)
                                     .spec["rate_per_s"] * 51)
    assert a[0] == 0.0 and a[-1] < 51
    ga, gb = np.diff(np.append(a, 51)), np.diff(np.append(b, 51))
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert np.allclose(reordered(seed, 2).due_times(51), b)


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 98765432101])
def test_order_seed_fixes_the_arrivals(seed):
    a, b = mix("poisson-vgg19", seed), mix("poisson-vgg19", seed + 1)
    assert np.array_equal(a.due_times(51), b.due_times(51))
    assert not np.array_equal(a.image(a.next_frame()),
                              b.image(b.next_frame()))


def test_same_seed_same_images():
    m1, m2 = mix("backlog", 7), mix("backlog", 7)
    ids = [m1.next_frame() for _ in range(5)]
    assert ids == [m2.next_frame() for _ in range(5)] == list(range(5))
    assert np.array_equal(m1.image(3), m2.image(3))
    assert not np.array_equal(m1.image(3), mix("backlog", 8).image(3))
    assert not np.array_equal(m1.image(3), m1.warmup_image(3))


def test_replay_repeats_half_of_every_round():
    m = Mix(REPLAY, 11, SHAPE)
    first = [m.next_frame() for _ in range(8)]
    assert len(set(first)) == 8
    prev = first
    for _ in range(5):
        cur = [m.next_frame() for _ in range(8)]
        assert sum(c == p for c, p in zip(cur, prev)) == 4
        assert len(set(cur)) == 8
        prev = cur


def test_warmup_covers_the_widths_the_window_drives():
    assert mix("poisson-vgg19", 1).warmup_steps(4) == \
        [(1, False), (2, False), (3, False), (4, False)]
    assert mix("backlog", 1).warmup_steps(8) == [(8, True)]
    assert Mix(REPLAY, 1, SHAPE).warmup_steps(8) == [(8, True)] * 2
