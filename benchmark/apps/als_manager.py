"""The static model manager the benchmark hands to ``ServingLayer``
(``oryx.serving.model-manager-class``): it builds the configuration's
synthetic ALS model from the seed and serves it, so that everything from
the HTTP door down is the program's own.

It does what ``ALSServingModelManager`` does on a MODEL message — build
an ``ALSServingModel`` from the same config keys, bulk-load items and
users, add known items, precompute solvers, measure the kernel route —
with factors drawn on the device in one jitted call and fetched once to
fill the store's host mirror (the store uploads from that mirror: there
is no device-resident load path yet, see PERF.md's open questions).
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from oryx_tpu.api.serving import ServingModelManager

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def known_item_counts(spec: dict, n_users: int,
                      rng: np.random.Generator) -> np.ndarray:
    """How many items each user already knows: a seeded log-normal with
    the configuration's median and sigma, clipped to [min, max]."""
    k = spec["known_items"]
    if k["distribution"] != "lognormal":
        raise ValueError(f"known-item distribution {k['distribution']!r}")
    draw = rng.lognormal(np.log(float(k["median"])), float(k["sigma"]),
                         n_users)
    return np.clip(np.rint(draw), int(k["min"]), int(k["max"])) \
        .astype(np.int64)


def _slabs(n_rows: int, limit: int = 2_000_000) -> int:
    """Into how many equal row slabs to draw ``n_rows``: the fewest that
    keep a slab's random bits small next to the matrix."""
    n = -(-n_rows // limit)
    while n_rows % n:
        n += 1
    return n


def draw_factors(key, n_rows: int, features: int, dtype):
    """``n_rows`` x ``features`` standard-normal factors in the served
    dtype, drawn on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    n_slabs = _slabs(n_rows)

    @jax.jit
    def draw(k):
        keys = jax.random.split(k, n_slabs)
        slabs = jax.lax.map(
            lambda kk: jax.random.normal(
                kk, (n_rows // n_slabs, features), jnp.float32
            ).astype(dtype), keys)
        return slabs.reshape(n_rows, features)

    return draw(key)


class SyntheticALSManager(ServingModelManager):
    """Builds the model in its constructor (``ServingLayer`` constructs
    the manager), records how long each step took in ``split``, and is
    read-only from then on: there is no update topic."""

    def __init__(self, config):
        path = config.get_string("oryx.benchmark.config-file")
        with open(os.path.join(ROOT, path), encoding="utf-8") as f:
            self.spec = json.load(f)
        self.seed = config.get_int("oryx.benchmark.seed")
        self.split: dict[str, float] = {}
        self.known_counts: np.ndarray | None = None
        self.solvers: dict[str, bool] = {}
        self.model = self._build(config)

    def consume(self, updates) -> None:
        for _ in updates:
            pass

    def get_model(self):
        return self.model

    def is_read_only(self) -> bool:
        return True

    def _mark(self, name: str, t: float) -> float:
        now = time.monotonic()
        self.split[name] = round(now - t, 3)
        return now

    def _build(self, config):
        import jax

        from oryx_tpu.app.als.feature_vectors import resolve_dtype
        from oryx_tpu.app.als.serving_model import ALSServingModel

        spec = self.spec
        features = int(spec["features"])
        n_items, n_users = int(spec["items"]), int(spec["users"])
        dtype_name = config.get_string("oryx.als.factor-dtype")
        dtype = resolve_dtype(dtype_name)
        # the same keys ALSServingModelManager reads, so the kernels,
        # mirrors and routing are whatever the program's config says
        model = ALSServingModel(
            features, bool(spec.get("implicit", True)),
            config.get_double("oryx.als.sample-rate"), None,
            dtype=dtype_name,
            item_shards=config.get_int("oryx.serving.api.item-shards"),
            int8_selection=config.get_string(
                "oryx.serving.api.int8-selection"),
            fold_scan=config.get_string("oryx.serving.api.fold-scan"))
        ky, kx = jax.random.split(jax.random.key(self.seed))

        t = time.monotonic()
        y_dev = draw_factors(ky, n_items, features, dtype)
        x_dev = draw_factors(kx, n_users, features, dtype)
        jax.block_until_ready((y_dev, x_dev))
        t = self._mark("draw_s", t)

        # the fetch (10 GB at some 0.4 GB/s) waits on the device, not on
        # the interpreter: ids and known items are built meanwhile
        fetched: dict = {}

        def fetch() -> None:
            fetched["y"], fetched["x"] = np.asarray(y_dev), np.asarray(x_dev)
            y_dev.delete()
            x_dev.delete()

        fetcher = threading.Thread(target=fetch, name="benchmark-fetch")
        fetcher.start()
        item_ids = [str(i) for i in range(n_items)]
        user_ids = [str(u) for u in range(n_users)]
        t = self._mark("ids_s", t)
        rng = np.random.default_rng([self.seed, 0x6B6E6F77])
        counts = known_item_counts(spec, n_users, rng)
        picks = rng.integers(0, n_items, int(counts.sum())).tolist()
        held, at = [], 0
        for u, n in enumerate(counts.tolist()):
            known = {item_ids[j] for j in picks[at:at + n]}
            model.add_known_items(user_ids[u], known)
            # a duplicate pick shrinks the set: count what the model holds
            held.append(len(known))
            at += n
        self.known_counts = np.asarray(held, dtype=np.int64)
        t = self._mark("known_items_s", t)
        fetcher.join()
        if "y" not in fetched:
            raise RuntimeError("fetching the drawn factors failed")
        t = self._mark("fetch_wait_s", t)

        model.bulk_load_items(item_ids, fetched.pop("y"))
        model.bulk_load_users(user_ids, fetched.pop("x"))
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
