"""The generator: the same seed gives the same inputs, another seed other
token ids on the same shape of work."""

import numpy as np

from benchmark import traffic as T

MIX = {"kind": "open_loop", "shape_seed": 5,
       "arrivals": {"rate_per_s": 4.0, "cv": 1.0},
       "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.9,
                         "min": 8, "max": 160},
       "answer_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                         "min": 2, "max": 16}}


def shape(plan):
    return [(p.due, len(p.prompt), p.max_new) for p in plan]


def test_same_seed_same_requests():
    big = 3_000_000_001             # more than 32 signed bits hold
    a = T.plan_requests(MIX, 509, big, 10.0)
    b = T.plan_requests(MIX, 509, big, 10.0)
    assert shape(a) == shape(b)
    assert all((x.prompt == y.prompt).all() and x.seed == y.seed
               for x, y in zip(a, b))


def test_other_seed_other_tokens_same_work():
    a = T.plan_requests(MIX, 509, 1, 10.0)
    b = T.plan_requests(MIX, 509, 2, 10.0)
    assert shape(a) == shape(b)
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert [x.seed for x in a] != [x.seed for x in b]


def test_open_loop_offers_its_rate_inside_the_window():
    plan = T.plan_requests(MIX, 509, 1, 10.0)
    dues = [p.due for p in plan]
    assert len(plan) == 40 and dues == sorted(dues)
    assert dues[0] == 0.0 and dues[-1] < 10.0
    assert all(8 <= len(p.prompt) <= 160 and 2 <= p.max_new <= 16
               for p in plan)


def test_backlog_is_all_due_at_zero():
    plan = T.plan_requests(dict(MIX, kind="backlog", requests=30), 509, 1, 5.0)
    assert len(plan) == 30 and {p.due for p in plan} == {0.0}


def test_train_batches_repeat_per_seed():
    mix = {"seq_len": 16, "dataset_batches": 3}
    a, b = T.train_batches(mix, 509, 9, 4), T.train_batches(mix, 509, 9, 4)
    c = T.train_batches(mix, 509, 10, 4)
    x, y, z = next(a)["input_ids"], next(b)["input_ids"], next(c)["input_ids"]
    assert x.shape == (4, 16) and x.dtype == np.int32
    assert (x == y).all() and (x != z).any()
    assert (x == x[:, :1]).all()          # constant-token sequences
    second, third, again = (next(a)["input_ids"] for _ in range(3))
    assert (second != x).any() and (again == x).all()     # epochs of three


def test_every_mix_file_loads_with_its_rehearsal():
    import glob
    import os

    for path in glob.glob(os.path.join(T.HERE, "traffic", "*.json")):
        name = os.path.basename(path)[:-5]
        assert T.load_mix(name)["kind"] == T.load_mix(name, True)["kind"]
