"""The one traffic generator: a mix file and a seed -> the fleet's inputs.

A mix (``traffic/<name>.json``) lists its ``members``, each the field
values of one workload (operation, access pattern, request size, streams,
working set, in-place share, read share, think time, burst duty cycle and
period). From ``--seed`` the generator draws:

* which member each client runs: equal shares (``n // members``, the
  remainder to the first members), in the order of a seeded permutation,
  so every seed gives the same amount of each kind of work;
* each client's stripe offset, uniform over the OSTs;
* the simulation seed, which drives the OST service noise.

A mix with a ``schedule`` (phased jobs; see ``spec.py``) also has, from
a stream of its own so that the draws above stay as they are:

* the sequence each job steps through: equal shares of the jobs (the
  remainder to the first sequences), in a seeded order;
* each job's starting phase, a whole segment: within each sequence the
  jobs take the phases in equal shares, in a seeded order, so every seed
  puts the same number of jobs on each member at every instant.

Every job then switches at the same simulated instants, the multiples of
``segment_s``. The client's member at time t is the one its schedule
gives, and the members drawn above are not used.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

_SIM_SEED_BITS = 62       # the simulation seed is drawn below 2**62
# draws of a mix's schedule come from a stream of their own
_SCHEDULE_STREAM = 0x5C4E


@dataclass
class FleetInputs:
    member_idx: np.ndarray     # (n,) member of each client
    stripe_offsets: np.ndarray  # (n,) first OST of each client's stripe
    sim_seed: int


def generate(n_clients: int, n_osts: int, n_members: int,
             seed: int) -> FleetInputs:
    # one stream, drawn in a fixed order: permutation, offsets, sim seed
    rng = np.random.Generator(np.random.PCG64(int(seed) & ((1 << 64) - 1)))
    shares = np.arange(n_clients) % n_members
    member_idx = shares[rng.permutation(n_clients)]
    offsets = rng.integers(0, n_osts, size=n_clients)
    sim_seed = int(rng.integers(0, 1 << _SIM_SEED_BITS))
    return FleetInputs(member_idx=member_idx.astype(np.int64),
                       stripe_offsets=offsets.astype(np.int64),
                       sim_seed=sim_seed)


@dataclass
class Schedule:
    """Phased jobs: which member every client runs at each instant."""
    sequences: np.ndarray      # (n_seq, length) member of each phase
    lengths: np.ndarray        # (n_seq,) phases of each sequence
    client_seq: np.ndarray     # (n,) sequence of each client's job
    client_start: np.ndarray   # (n,) starting phase of each client's job
    job_of: np.ndarray         # (n,) job of each client
    segment_s: float

    def switch(self, k: int) -> float:
        """The instant of the ``k``-th switch (k >= 1)."""
        return k * self.segment_s

    def phase(self, t: float) -> int:
        """Switches made by time ``t``: the ``k``-th applies from the
        instant ``switch(k)`` on."""
        k = max(int(math.floor(t / self.segment_s)), 0)
        while self.switch(k + 1) <= t:
            k += 1
        while k > 0 and self.switch(k) > t:
            k -= 1
        return k

    def member_at(self, t: float, clients=slice(None)) -> np.ndarray:
        """The member each client (or each of ``clients``) runs in the
        interval that starts at ``t``."""
        seq = self.client_seq[clients]
        pos = (self.client_start[clients] + self.phase(t)) % self.lengths[seq]
        return self.sequences[seq, pos].astype(np.int64)

    def next_switch(self, t: float) -> float:
        """The first switch at or after ``t``."""
        k = self.phase(t)
        return t if k and self.switch(k) == t else self.switch(k + 1)


def schedule(n_clients: int, plan: Dict, member_names: Sequence[str],
             seed: int) -> Schedule:
    """A mix's ``schedule`` (already checked by ``spec``) for ``n_clients``
    clients, drawn from ``seed``."""
    rng = np.random.Generator(np.random.PCG64(
        [int(seed) & ((1 << 64) - 1), _SCHEDULE_STREAM]))
    index = {name: i for i, name in enumerate(member_names)}
    seqs: List[List[int]] = [[index[m] for m in members]
                             for members in plan["sequences"].values()]
    lengths = np.array([len(q) for q in seqs], dtype=np.int64)
    table = np.zeros((len(seqs), int(lengths.max())), dtype=np.int64)
    for q, members in enumerate(seqs):
        table[q, :len(members)] = members
    k = int(plan["job_clients"])
    n_jobs = -(-n_clients // k)
    job_seq = (np.arange(n_jobs) % len(seqs))[rng.permutation(n_jobs)]
    job_start = np.zeros(n_jobs, dtype=np.int64)
    for q in range(len(seqs)):
        jobs = np.flatnonzero(job_seq == q)
        job_start[jobs] = (np.arange(jobs.size)
                           % lengths[q])[rng.permutation(jobs.size)]
    job_of = np.arange(n_clients) // k
    return Schedule(sequences=table, lengths=lengths,
                    client_seq=job_seq[job_of], client_start=job_start[job_of],
                    job_of=job_of, segment_s=float(plan["segment_s"]))
