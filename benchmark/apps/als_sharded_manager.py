"""The static model manager of the sharded ALS cell: ``als_manager.py``'s,
with the item factors drawn on EACH device for the rows it will hold.

``SyntheticALSManager`` draws the whole item matrix in one jitted call on
one device; 20M x 250 float32 are 20 GB and a chip has 16.  Here every
device of the mesh draws an equal slab of rows from its own fold of the
seed's key, the slabs are fetched one after the other (two at a time at
most, 5 GB each, beside the store's 20 GB host mirror) and handed to the model by the
same calls ``ALSServingModelManager`` makes on a MODEL message — build
the ``ALSServingModel`` from the program's config keys, ``bulk_load``
items and users, add known items, precompute solvers, refresh the route
— so the store uploads from its mirror, row-sharded, as it would in
``python -m oryx_tpu serving``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.apps.als_manager import (SyntheticALSManager, draw_factors,
                                        known_item_counts)


def resident_gb() -> dict:
    """The process's resident set now, in GB, split as far as this
    kernel splits it: ``/proc/self/smaps_rollup`` (anonymous pages are
    the process's own heap, the mirror and the slabs among them; the
    rest is mapped files and shared memory), else ``VmRSS`` alone."""
    keys = {"Rss": "rss", "Anonymous": "anonymous",
            "Pss_Shmem": "shmem", "Pss_File": "file"}
    try:
        with open("/proc/self/smaps_rollup", encoding="ascii") as f:
            rows = dict(line.split(":", 1) for line in f if ":" in line)
        return {keys[k]: round(int(rows[k].split()[0]) / 1e6, 2)
                for k in keys if k in rows}
    except (OSError, ValueError):
        pass
    try:
        with open("/proc/self/status", encoding="ascii") as f:
            rows = dict(line.split(":", 1) for line in f)
        return {"rss": round(int(rows["VmRSS"].split()[0]) / 1e6, 2)}
    except (OSError, KeyError, ValueError):
        return {}


class ShardedSyntheticALSManager(SyntheticALSManager):
    def __init__(self, config):
        # the resident set at the end of every step of the load, and
        # after each slab: where the host's peak comes from
        self.host_resident_gb: dict = {}
        super().__init__(config)

    def _mark(self, name: str, t: float) -> float:
        self.host_resident_gb[name[:-2]] = resident_gb()
        return super()._mark(name, t)

    def _build(self, config):
        import jax

        from oryx_tpu.app.als.feature_vectors import resolve_dtype
        from oryx_tpu.app.als.serving_model import ALSServingModel

        spec = self.spec
        features = int(spec["features"])
        n_items, n_users = int(spec["items"]), int(spec["users"])
        dtype_name = config.get_string("oryx.als.factor-dtype")
        dtype = resolve_dtype(dtype_name)
        shards = config.get_int("oryx.serving.api.item-shards")
        if n_items % shards:
            raise ValueError(f"{n_items} items do not split over {shards} "
                             "devices")
        model = ALSServingModel(
            features, bool(spec.get("implicit", True)),
            config.get_double("oryx.als.sample-rate"), None,
            dtype=dtype_name, item_shards=shards,
            int8_selection=config.get_string(
                "oryx.serving.api.int8-selection"),
            fold_scan=config.get_string("oryx.serving.api.fold-scan"))
        ky, kx = jax.random.split(jax.random.key(self.seed))

        t = time.monotonic()
        slab = n_items // shards
        drawn = []
        for n, dev in enumerate(jax.devices()[:shards]):
            with jax.default_device(dev):
                drawn.append(draw_factors(jax.random.fold_in(ky, n), slab,
                                          features, dtype))
        x_dev = draw_factors(kx, n_users, features, dtype)
        jax.block_until_ready((drawn, x_dev))
        t = self._mark("draw_s", t)

        # the store's mirror is sized once, and each fetched slab goes
        # into it and is dropped: the host never holds two copies
        item_ids = [str(i) for i in range(n_items)]
        user_ids = [str(u) for u in range(n_users)]
        t = self._mark("ids_s", t)
        model.Y.reserve(n_items)
        loaded: dict = {}

        def load() -> None:
            for n, part in enumerate(drawn):
                # the next slab crosses to the host while this one is
                # loaded: two slabs on the host at most
                for ahead in drawn[n:n + 2]:
                    ahead.copy_to_host_async()
                rows = np.asarray(part)
                part.delete()
                self.host_resident_gb[f"slab{n}_fetched"] = resident_gb()
                model.bulk_load_items(item_ids[n * slab:(n + 1) * slab],
                                      rows)
                self.host_resident_gb[f"slab{n}_loaded"] = resident_gb()
            loaded["x"] = np.asarray(x_dev)
            x_dev.delete()

        # the fetches wait on the devices, not on the interpreter:
        # known items are built meanwhile
        loader = threading.Thread(target=load, name="benchmark-load")
        loader.start()
        rng = np.random.default_rng([self.seed, 0x6B6E6F77])
        counts = known_item_counts(spec, n_users, rng)
        picks = rng.integers(0, n_items, int(counts.sum())).tolist()
        held, at = [], 0
        for u, n in enumerate(counts.tolist()):
            known = {item_ids[j] for j in picks[at:at + n]}
            model.add_known_items(user_ids[u], known)
            held.append(len(known))
            at += n
        self.known_counts = np.asarray(held, dtype=np.int64)
        t = self._mark("known_items_s", t)
        loader.join()
        if "x" not in loaded:
            raise RuntimeError("fetching and loading the drawn factors "
                               "failed")
        model.bulk_load_users(user_ids, loaded.pop("x"))
        t = self._mark("bulk_load_s", t)
        model.Y.device_arrays()
        model.X.device_arrays()
        model.Y.row_ids()
        t = self._mark("upload_s", t)

        model.precompute_solvers()
        self.solvers = {"yty": model.get_yty_solver(blocking=True) is not None,
                        "xtx": model.get_xtx_solver(blocking=True) is not None}
        t = self._mark("solvers_s", t)
        model.refresh_route()
        self._mark("route_s", t)
        return model
