"""The ALS application under LSH (``oryx.als.sample-rate`` < 1 in the
configuration's ``serving_config``): a configuration whose ``app`` is
``"als_lsh"`` is served, warmed and checked by this file.

Everything is ``apps/als.py``'s — the overlay and its manager (which
hands ``oryx.als.sample-rate`` to the model and loads through
``bulk_load_items``; ``als_lsh_manager.py`` only makes the hyperplanes a
function of the seed), the population, the warm-up through
``model.top_n_batch``, the precheck over HTTP, the sampled check of the
window — but for three things: the reference is the pruned one
(``als_lsh_reference.py``: buckets, Hamming ball, candidates, the
marginal-bit rule); the deployment's partitioning is asserted before
anything is measured; and ``store()`` describes the rows a MEAN WINDOW's
candidates come to, so that the two roofline metrics read the bytes the
semantics oblige a window to read over the time its program took (with
the whole store's rows a pruned pass would read some 280% of its
roofline).
"""

from __future__ import annotations

import numpy as np

from benchmark import costs
from benchmark.apps import als
from benchmark.apps.als import population  # noqa: F401 — the harness asks
from benchmark.apps.als_lsh_reference import BIT_MARGIN, LshReference

MANAGER = "benchmark.apps.als_lsh_manager.SeededLshALSManager"


def overlay(cell, seed: int) -> dict:
    """``als.overlay``.  A program whose LSH is a mask over the whole
    store (before PR 36) streams the exact scan's bytes whatever the
    sample rate says, and its router then serves the exact scan: it
    cannot run this deployment, and says so here, before anything is
    built (the same benchmark files are laid over a parent checkout)."""
    from oryx_tpu.app.als.feature_vectors import FeatureVectorStore

    if not hasattr(FeatureVectorStore, "partition_by"):
        raise SystemExit(
            f"benchmark: cell {cell.name} needs an item store laid out by "
            "LSH bucket (oryx_tpu/app/als/feature_vectors.py "
            "FeatureVectorStore.partition_by); this program's LSH is a "
            "mask over the whole store and cannot prune bytes")
    return dict(als.overlay(cell, seed),
                **{"oryx.serving.model-manager-class": MANAGER})


class Checker(als.Checker):
    def __init__(self, layer, cell, seed: int):
        super().__init__(layer, cell, seed)
        want = cell.config["lsh"]
        have = self.model.partitioning()
        if have is None or (have["hashes"], have["radius"]) != (
                int(want["hashes"]), int(want["radius"])):
            raise SystemExit(
                f"benchmark: cell {cell.name} is the deployment with "
                f"{want['hashes']} hyperplanes and radius {want['radius']}; "
                f"the model's partitioning is {have}")
        route = self.model.metrics().get("kernel_route") or {}
        if not route.get("use_lsh"):
            raise SystemExit(
                f"benchmark: cell {cell.name}: the model's route does not "
                f"prune ({route})")
        self.reference = LshReference(self.model, want["hashes"],
                                      want["radius"])
        self._readings: list[dict] = []

    def warm(self) -> list[tuple[int, int]]:
        """``als.Checker.warm`` (a pruned pass's grid and loops run to a
        bound the device computes, so a (window, top-k) pair is ONE
        program however many steps a window visits), then the exact scan
        over a window's candidates for every pair: the program a failed
        certificate would need."""
        import jax

        pairs = super().warm()
        for w, k in pairs:
            jax.device_get(self.model._enqueue_exact(
                np.zeros((w, self.model.features), np.float32), k, 0, w))
        return pairs

    def precheck(self) -> list[str]:
        # the layout first: it names a hashing fault in one line where
        # the answers would name it thirty-two times
        return self.reference.layout_problems() + super().precheck()

    def counters(self) -> dict:
        m = self.model
        out = dict(super().counters(),
                   lsh_windows=int(m.lsh_windows),
                   lsh_candidate_rows=int(m.lsh_candidate_rows),
                   lsh_streamed_rows=int(m.lsh_streamed_rows),
                   lsh_row_moves=int(m.lsh_row_moves))
        self._readings.append(out)
        return out

    def store(self) -> dict:
        """The rows a mean window of the measured window had to read:
        the live rows inside its requests' Hamming balls (the harness
        read the counters at the window's two edges), with the store's
        stored features and item size.  ``costs.scan_window`` over them
        is the yardstick, whatever implements the pass."""
        first, last = self._readings[-2], self._readings[-1]
        windows = last["lsh_windows"] - first["lsh_windows"]
        rows = last["lsh_candidate_rows"] - first["lsh_candidate_rows"]
        return dict(super().store(), rows=rows / max(1, windows))

    def detail(self) -> dict:
        ref = self.reference
        recall = ref.recall()
        return dict(
            super().detail(),
            partitioning=self.model.partitioning(),
            lsh={"bit_margin": BIT_MARGIN,
                 # readings that judge nothing
                 "answers_that_met_a_marginal_bit": ref.met_marginal_bit,
                 "live_rows_with_a_marginal_bit": ref.marginal_rows,
                 "largest_bit_program_and_reference_differ_on":
                     ref.worst_flipped_bit,
                 "recall_at_%d" % self.how_many:
                     None if recall is None else round(recall, 4)},
            counters=self._readings[-1] if self._readings else None,
            window_ladder=list(costs.WINDOW_LADDER))
