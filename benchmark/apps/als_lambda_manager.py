"""The model manager of the ``als_lambda`` application: the synthetic
model of ``als_manager.SyntheticALSManager`` (same seed, same factors,
same known items, so a static configuration with the same numbers is its
control), kept fresh by the update topic.

Building is the base class's.  From then on every record of the update
topic goes through the PROGRAM's ``ALSServingModelManager.consume`` /
``consume_key_message`` (``UP`` parsing, ``set_user_vector`` /
``set_item_vector``, known items, the ``batch`` tag), pointed at the
model built here.  Two things are added for the checks: the stored value
a row had before its first update (``before``), which nothing can
recompute once 10 GB of factors have been overwritten in place, and when
each micro-batch's records were applied (``batch_applied_ms``).
"""

from __future__ import annotations

import json
import time

from benchmark.apps.als_manager import SyntheticALSManager

from oryx_tpu.app.als.serving_manager import ALSServingModelManager
from oryx_tpu.kafka.api import KEY_UP


class SyntheticALSLambdaManager(SyntheticALSManager):

    def __init__(self, config):
        super().__init__(config)
        self.program = ALSServingModelManager(config)
        self.program.model = self.model
        # the load-fraction trigger's work (solvers, route) is done
        self.program._triggered_solver = True
        # ("X"|"Y", id) -> the stored vector before its first update,
        # None for an id new to the model
        self.before: dict = {}
        # micro-batch number (the records' ``batch`` header) -> wall
        # clock ms at which its latest record was applied
        self.batch_applied_ms: dict = {}

    def consume(self, updates) -> None:
        self.program.consume(self._remembering(updates))

    def _remembering(self, updates):
        for km in updates:
            if km.key == KEY_UP:
                # the head of ["X"|"Y", id, [...]: two short strings
                kind, id_ = json.loads(
                    km.message[:km.message.index(",", km.message.index(",")
                                                 + 1)] + "]")
                if (kind, id_) not in self.before:
                    store = self.model.X if kind == "X" else self.model.Y
                    self.before[(kind, id_)] = store.get_vector(id_)
            yield km
            if km.key == KEY_UP and km.headers and "batch" in km.headers:
                # back from the program's consume: the record is applied
                self.batch_applied_ms[km.headers["batch"]] = \
                    time.time() * 1e3

    def is_read_only(self) -> bool:
        return False
