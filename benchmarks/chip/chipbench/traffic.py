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
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_SIM_SEED_BITS = 62       # the simulation seed is drawn below 2**62


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
