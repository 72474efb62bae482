"""The fleet traffic generator: every call of a fleet cell from a
configuration file, a traffic file and ``--seed``.

A call is one fleet episode: ``jobs_per_call`` jobs arrive in a burst over
the first ``arrival_span`` slots of a ``horizon``-slot window of the
configuration's market, all contend for its one spot pool, and each job is
admitted on a lane of the configuration's pool drawn i.i.d. from uniform
selector weights (the fleet before the selector has learned). The window's
start and its forecast's noise seed are drawn per call. Call ``i`` is drawn
from its own generator, seeded by ``(seed, i)``, so the calls of a run do
not depend on how many came before, and the same seed gives the same
inputs. Every call has the same sizes; only the draws differ.
"""
from __future__ import annotations

import numpy as np

from bench.gen.jobs import draw_jobs
from bench.gen.market import spot_trace
from bench.gen.traffic import pool_lanes, seed_words

_TAG_MARKET, _TAG_CALL, _TAG_WARM, _TAG_SAMPLE = 11, 12, 13, 14


class Traffic:
    """All inputs of one run of a fleet cell, drawn on demand from the
    seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.lanes = pool_lanes(config["pool"])
        mseed = int(np.random.default_rng(
            seed_words(seed, _TAG_MARKET)).integers(2**63))
        self.prices, self.avail = spot_trace(mseed, **config["market"])
        self.deadline = int(config["jobs"]["deadline"])
        self.horizon = int(traffic["horizon"])
        self.arrival_span = int(traffic["arrival_span"])
        if self.arrival_span - 1 + self.deadline > self.horizon:
            raise ValueError("a job's deadline runs past the horizon")
        # the forecast needs the slot after the window's last
        if len(self.prices) < self.horizon + 2:
            raise ValueError("market trace shorter than one call's window")

    @property
    def jobs_per_call(self) -> int:
        return int(self.traffic["jobs_per_call"])

    def decisions_per_call(self) -> int:
        """Each job's allocation in each slot of its deadline."""
        return self.jobs_per_call * self.deadline

    def call(self, i: int) -> dict:
        return self._draw(np.random.default_rng(
            seed_words(self.seed, _TAG_CALL, i)))

    def warmup(self, i: int) -> dict:
        return self._draw(np.random.default_rng(
            seed_words(self.seed, _TAG_WARM, i)))

    def sample_rng(self) -> np.random.Generator:
        """The generator that picks which calls the check compares."""
        return np.random.default_rng(seed_words(self.seed, _TAG_SAMPLE))

    def _draw(self, rng: np.random.Generator) -> dict:
        k = self.jobs_per_call
        jobs = draw_jobs(rng, k, self.config["jobs"])
        return {
            "jobs": jobs,
            "arrivals": rng.integers(0, self.arrival_span, size=k,
                                     dtype=np.int32),
            "lanes": rng.integers(0, len(self.lanes), size=k),
            "t0": int(rng.integers(0, len(self.prices) - self.horizon - 1)),
            "noise_seed": int(rng.integers(0, 2**62)),
        }
