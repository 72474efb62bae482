"""The one traffic generator: builds every call of a cell from a
configuration file, a traffic file and ``--seed``.

A traffic file gives the sizes of one call, a batch of jobs each simulated
under every lane of the pool; the configuration gives the pool, the job
distribution, the market regime and the noise model. Call ``i`` of a run
is drawn from its own generator, seeded by ``(seed, i)``, so the calls of
a run do not depend on how many came before, and the same seed gives the
same inputs.

Every call of a cell has the same sizes (jobs per call, slots, lanes); only
the draws differ, so seeds reorder the work and never resize it.
"""
from __future__ import annotations

import math

import numpy as np

from bench.gen.jobs import draw_jobs
from bench.gen.market import spot_trace

# kind codes of the lane encoding (names are what the config files use)
KINDS = ("ahap", "ahanp", "od_only", "msu", "up", "rand_deadline")

_TAG_MARKET, _TAG_CALL, _TAG_WARM, _TAG_SAMPLE = 1, 2, 3, 4


def seed_words(seed: int, *tags: int):
    """A SeedSequence entropy list for any whole ``seed`` (negative or wider
    than 64 bits included) and stream tags."""
    seed = int(seed)
    return [seed & (2**64 - 1), (seed >> 64) & (2**64 - 1), int(seed < 0),
            *tags]


def pool_lanes(pool: list) -> list:
    """Expand a config's ``pool`` groups into one dict per policy lane, in
    order: ``{"kind", "omega", "v", "sigma", "cfrac"}``.

    Groups: ``{"kind": "ahap", "omega": [..], "sigma": [..]}`` gives every
    (omega, v <= omega, sigma); ``{"kind": "ahanp", "sigma": [..]}``;
    ``{"kind": "rand_deadline", "q": [..]}`` with the ski-rental-optimal
    commitment fraction log(1 + q (e - 1)); and a plain ``{"kind": k}`` for
    the baselines."""
    lanes = []
    for g in pool:
        kind = g["kind"]
        if kind not in KINDS:
            raise ValueError(f"unknown lane kind {kind!r}")
        if kind == "ahap":
            for w in g["omega"]:
                for v in range(1, w + 1):
                    for s in g["sigma"]:
                        lanes.append(dict(kind=kind, omega=w, v=v, sigma=s,
                                          cfrac=0.0))
        elif kind == "ahanp":
            lanes += [dict(kind=kind, omega=0, v=1, sigma=s, cfrac=0.0)
                      for s in g["sigma"]]
        elif kind == "rand_deadline":
            lanes += [dict(kind=kind, omega=0, v=1, sigma=q,
                           cfrac=float(np.log1p(q * (math.e - 1.0))))
                      for q in g["q"]]
        else:
            lanes.append(dict(kind=kind, omega=0, v=1, sigma=0.0, cfrac=0.0))
    return lanes


def lane_arrays(lanes: list) -> dict:
    """Lane dicts as arrays: ``kind`` as an index into :data:`KINDS`."""
    return {
        "kind": np.array([KINDS.index(l["kind"]) for l in lanes], np.int32),
        "omega": np.array([l["omega"] for l in lanes], np.int32),
        "v": np.array([l["v"] for l in lanes], np.int32),
        "sigma": np.array([l["sigma"] for l in lanes], np.float32),
        "cfrac": np.array([l["cfrac"] for l in lanes], np.float32),
    }


class Traffic:
    """All inputs of one run of a cell, drawn on demand from the seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.lanes = pool_lanes(config["pool"])
        m = config["market"]
        mseed = int(np.random.default_rng(
            seed_words(seed, _TAG_MARKET)).integers(2**63))
        self.prices, self.avail = spot_trace(mseed, **m)
        self.deadline = int(config["jobs"]["deadline"])
        if len(self.prices) < self.deadline + 2:
            raise ValueError("market trace shorter than one call's window")

    @property
    def jobs_per_call(self) -> int:
        return int(self.traffic["jobs_per_call"])

    def decisions_per_call(self) -> int:
        """Every (job, lane, slot) cell of the call."""
        return self.jobs_per_call * len(self.lanes) * self.deadline

    def call(self, i: int) -> dict:
        return self._draw(np.random.default_rng(
            seed_words(self.seed, _TAG_CALL, i)))

    def warmup(self, i: int) -> dict:
        return self._draw(np.random.default_rng(
            seed_words(self.seed, _TAG_WARM, i)))

    def sample_rng(self) -> np.random.Generator:
        """The generator that picks which answers the check compares."""
        return np.random.default_rng(seed_words(self.seed, _TAG_SAMPLE))

    def _draw(self, rng: np.random.Generator) -> dict:
        k = self.jobs_per_call
        jobs = draw_jobs(rng, k, self.config["jobs"])
        # each job's window is its deadline plus one slot, as the forecast
        # needs the slot after the last decision
        t0s = rng.integers(0, len(self.prices) - self.deadline - 1, size=k)
        return {"jobs": jobs, "t0s": t0s,
                "noise_seeds": rng.integers(0, 2**62, size=k)}
