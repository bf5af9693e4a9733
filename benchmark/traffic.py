"""The one traffic generator. A mix is a data file of parameters under
``benchmark/traffic/``; nothing here knows a mix by name.

Every seed gets the same work: the lengths, the arrival times and their
order are drawn from the mix's own ``shape_seed``; ``--seed`` draws the token
ids and the per-request sampling seeds (and, in the kinds, the weights). A
cell is one fixed realisation of its arrival process, replayed: on a system
whose iteration takes 0.1 s a tail over a hundred requests moves by whole
iterations when the order changes, and the spread between seeds would then
be the dice's and not the system's (PERF.md, PR 24).
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, rehearse: bool = False) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if rehearse:
        mix = merged(mix, mix.get("rehearsal", {}))
    return mix


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def rng_for(seed: int) -> np.random.Generator:
    # --seed may be a little over 2**31; numpy's SeedSequence takes any size
    return np.random.default_rng(int(seed))


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from ``spec``: lognormal (median, sigma) or fixed,
    clipped to [min, max]."""
    if spec["dist"] == "fixed":
        x = np.full(n, float(spec["value"]))
    elif spec["dist"] == "lognormal":
        x = float(spec["median"]) * np.exp(
            float(spec["sigma"]) * rng.standard_normal(n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(float(spec["min"]), float(spec["max"]), n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec.get("min", 1),
                   spec.get("max", np.inf)).astype(np.int64)


def draw_due_times(arrivals: dict, seconds: float,
                   shape_rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds) of an open loop at ``rate_per_s``: gamma
    gaps with coefficient of variation ``cv`` (1 = Poisson), scaled to fill
    the window exactly, so that the rate offered is the rate stated."""
    n = max(1, int(round(float(arrivals["rate_per_s"]) * seconds)))
    cv = float(arrivals.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    gaps = shape_rng.gamma(shape, 1.0 / shape, n)
    return (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())


@dataclasses.dataclass
class Planned:
    """One request as the generator planned it."""

    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int
    seed: int                  # the request's sampling seed


def plan_requests(mix: dict, vocab_size: int, seed: int,
                  seconds: float) -> list[Planned]:
    """The cell's requests in due order. ``backlog``: ``requests`` of them,
    all due at 0. ``open_loop``: as many as the rate puts into the window."""
    shape_rng = rng_for(mix["shape_seed"])
    rng = rng_for(seed)
    if mix["kind"] == "backlog":
        due = np.zeros(int(mix["requests"]))
    elif mix["kind"] == "open_loop":
        due = draw_due_times(mix["arrivals"], seconds, shape_rng)
    else:
        raise ValueError(f"{mix['kind']!r} is not a serving mix")
    n = len(due)
    prompts = draw_lengths(mix["prompt_tokens"], n, shape_rng)
    answers = draw_lengths(mix["answer_tokens"], n, shape_rng)
    seeds = rng.integers(0, 2 ** 31 - 1, n)
    return [Planned(float(due[i]),
                    rng.integers(0, vocab_size, int(prompts[i]),
                                 dtype=np.int32),
                    int(answers[i]), int(seeds[i])) for i in range(n)]


def train_batches(mix: dict, vocab_size: int, seed: int, rows: int):
    """Endless (rows, seq_len) int32 batches: epochs over a data set of
    ``dataset_batches`` batches of constant-token sequences, the token of
    each sequence drawn from the seed (``runtime/dataloader.
    random_token_dataset(learnable=True)`` copied). A small fixed data set is
    learned within a few epochs whatever the model's size, so a loss that
    falls far, not by a hair, shows the optimizer is really stepping."""
    rng = rng_for(seed)
    seq = int(mix["seq_len"])
    pool = [np.broadcast_to(rng.integers(0, vocab_size, (rows, 1),
                                         dtype=np.int32), (rows, seq)).copy()
            for _ in range(int(mix["dataset_batches"]))]
    while True:
        for ids in pool:
            yield {"input_ids": ids}
