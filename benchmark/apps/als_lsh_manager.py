"""``als_manager.SyntheticALSManager`` with the deployment's hyperplanes
drawn from the seed.

The hyperplanes are the deployment's weights, as the factors are: the
same ``--seed`` has to give the same buckets.  The model draws them from
the program's ``RandomManager`` when it is constructed, which hands out
generators seeded from the system's entropy outside the tests, and
``als_manager.py`` (which may not be edited) constructs the model with
the arguments ``ALSServingModelManager`` would: so for the length of the
build the generator the program asks for is one seeded from the
benchmark's seed.  Nothing else of the build draws from it.
"""

from __future__ import annotations

import numpy as np

from benchmark.apps.als_manager import SyntheticALSManager
from oryx_tpu.common.rand import RandomManager


class SeededLshALSManager(SyntheticALSManager):
    def _build(self, config):
        seed = self.seed
        real = RandomManager.__dict__["random"]
        RandomManager.random = classmethod(
            lambda cls: np.random.default_rng([seed, 0x4C5348]))
        try:
            return super()._build(config)
        finally:
            RandomManager.random = real
