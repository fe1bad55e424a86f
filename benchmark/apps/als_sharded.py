"""The ALS application over a catalog ROW-SHARDED across the chips of one
host (``oryx.serving.api.item-shards`` in the configuration's
``serving_config``): a configuration whose ``app`` is ``"als_sharded"`` is
served, warmed and checked by this file.

Everything is ``apps/als.py``'s — the overlay, the population, the warm-up
through ``model.top_n_batch``, the precheck over HTTP, the sampled check of
the window — but for three things: the manager draws the factors on each
device for its own rows (``als_sharded_manager.py``: 20 GB of float32 do not
fit the one device ``als_manager.py`` draws on); the reference runs shard
by shard (``als_sharded_reference.py``); and ``store()`` describes the rows
ONE chip holds, so that the roofline metrics read each chip's program
against one chip's peaks.
"""

from __future__ import annotations

import resource

from benchmark.apps import als
from benchmark.apps.als import population  # noqa: F401 — the harness asks
from benchmark.apps.als_sharded_manager import resident_gb
from benchmark.apps.als_sharded_reference import ShardedReference, _shards

MANAGER = "benchmark.apps.als_sharded_manager.ShardedSyntheticALSManager"


def overlay(cell, seed: int) -> dict:
    """``als.overlay`` with the sharded manager.  A program whose sharded
    path is still the flat matmul + ``top_k`` over a whole shard (before
    PR 34) cannot run this deployment, and says so here, before anything
    is built: the same benchmark files are laid over a parent checkout."""
    from oryx_tpu.app.als import serving_model

    if not hasattr(serving_model, "shard_plan"):
        raise SystemExit(
            f"benchmark: cell {cell.name} needs the two-phase scan inside "
            "the sharded program (oryx_tpu/app/als/serving_model.py "
            "shard_plan); this program does not have it")
    return dict(als.overlay(cell, seed),
                **{"oryx.serving.model-manager-class": MANAGER})


class Checker(als.Checker):
    def __init__(self, layer, cell, seed: int):
        super().__init__(layer, cell, seed)
        self.reference = ShardedReference(self.model)

    def counters(self) -> dict:
        return dict(super().counters(),
                    sharded_windows=int(self.model.sharded_windows),
                    shard_fallback_rows=int(self.model.shard_fallback_rows))

    def store(self) -> dict:
        """The rows the FULLEST chip holds of the served item matrix,
        with its stored features and item size: every chip runs the scan
        over its own rows at once, so one chip's bytes against one
        chip's peaks is each program's roofline."""
        vecs, _ = self.model.Y.device_arrays()
        return {"rows": max(int(part.shape[0]) for _, part in _shards(vecs)),
                "device_features": int(vecs.shape[1]),
                "itemsize": int(vecs.dtype.itemsize)}

    def detail(self) -> dict:
        import jax

        return dict(
            super().detail(),
            shards=[int(part.shape[0]) for _, part in _shards(
                self.model.Y.device_arrays()[0])],
            device_peak_bytes=[
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.local_devices()],
            host_peak_rss_bytes=1024 * resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss,
            # the resident set (GB) at the end of each step of the
            # load, and now, after the window
            host_resident_gb=dict(self.manager.host_resident_gb,
                                  now=resident_gb()),
            counters=self.counters())
