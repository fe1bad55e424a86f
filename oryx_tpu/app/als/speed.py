"""ALS speed layer: in-memory factor model + micro-batch fold-in.

Reference: app/oryx-app/src/main/java/com/cloudera/oryx/app/speed/als/
ALSSpeedModel.java:40-183 (X/Y partitioned vectors, expected-ID
accounting, cached XtX/YtY solvers) and ALSSpeedModelManager.java:60-231
(consume MODEL/UP; buildUpdates: timestamp-sort, delete-aware aggregate,
then one fold-in solve per event on a parallelStream).

TPU-native: buildUpdates aggregates the micro-batch on host, then folds
ALL user-side updates in one batched device solve and all item-side
updates in another (ops/als_fold_in.fold_in_batch) — two kernel launches
per micro-batch instead of two host solves per event.

Co-located with a serving layer (one process holds the chip:
``SpeedLayer(config, serving=layer)`` calls ``attach_serving``) the
manager keeps no stores of its own: its model is a view over the
serving model's X, Y and solver caches, so there is ONE copy of the
catalog on the device and one host mirror.  The serving layer's update
consumer is then the only writer, and this manager ignores the UP
records it would otherwise apply a second time.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Iterable, Sequence

import numpy as np

from ...api.speed import AbstractSpeedModelManager, SpeedModel
from ...common import pmml as pmml_io
from ...common import text as text_utils
from ...common.config import Config
from ...common.lang import BackgroundShare, RateLimitCheck
from ...kafka.api import KEY_MODEL, KEY_MODEL_REF, KEY_UP, KeyMessage
from ...obs import trace as obstrace
from ...ops import als_fold_in
from ..pmml_utils import read_pmml_from_update_key_message
from . import common as als_common
from . import slices
from .factor_model import FactorModelBase

_log = logging.getLogger(__name__)

__all__ = ["ALSSpeedModel", "ALSSpeedModelManager"]


class ALSSpeedModel(FactorModelBase, SpeedModel):
    """User/item factor stores with cached Gramian solvers."""

    def __init__(self, features: int, implicit: bool, log_strength: bool,
                 epsilon: float, resident=None):
        super().__init__(features, implicit, resident=resident)
        self.log_strength = log_strength
        self.epsilon = epsilon

    def __repr__(self):  # pragma: no cover
        return (f"ALSSpeedModel[features:{self.features}, "
                f"X:({len(self.X)} users), Y:({len(self.Y)} items)]")


class ALSSpeedModelManager(AbstractSpeedModelManager):
    """Consumes MODEL/UP messages; folds new input into factor deltas."""

    def __init__(self, config: Config):
        self.model: ALSSpeedModel | None = None
        self.no_known_items = config.get_bool("oryx.als.no-known-items")
        self.min_model_load_fraction = config.get_double(
            "oryx.speed.min-model-load-fraction")
        if not 0.0 <= self.min_model_load_fraction <= 1.0:
            raise ValueError("min-model-load-fraction must be in [0,1]")
        # ring-sharded fold-in (oryx.speed.shard = "i/N"): the model
        # state stays FULL — Gramian solvers need the whole catalog and
        # the consume thread applies every UP/MODEL record — but
        # build_updates folds only events whose ITEM this worker owns
        # on the serving murmur2 ring, so N workers split the fold-in
        # work by item slice exactly as replicas split scoring
        shard_spec = config.get_optional_string("oryx.speed.shard")
        if shard_spec:
            from ...cluster.sharding import parse_shard_spec
            self.shard_index, self.shard_count = parse_shard_spec(shard_spec)
        else:
            self.shard_index, self.shard_count = 0, 1
        self.skipped_remote_events = 0
        self._log_rate_limit = RateLimitCheck(60.0)
        # integrity counters (mirrors the serving manager)
        self.rejected_updates = 0
        self.rejected_models = 0
        # sharded model distribution (slices.py): the speed layer folds
        # against the FULL catalog, so it bulk-loads every slice — far
        # cheaper than parsing the per-row UP stream the sharded
        # publisher no longer sends
        self.slice_loads = 0
        self.slice_load_fallbacks = 0
        self.model_load_s = 0.0
        # input records handed to build_updates, and how many of its
        # calls found something to fold
        self.events_folded = 0
        self.micro_batches = 0
        # co-located mode: the serving manager whose model this one
        # folds in against (attach_serving), and the hyperparameters
        # only a MODEL document carries
        self.last_batch: dict = {}
        self._serving = None
        # None in a process of its own: nothing to be considerate of
        self.pace: BackgroundShare | None = None
        self._log_strength = False
        self._epsilon = float("nan")

    def attach_serving(self, serving_manager) -> None:
        """Fold in against the model ``serving_manager`` serves, in
        this process, instead of a second copy of it (module docstring).
        From here on UP records are the serving consumer's to apply."""
        self._serving = serving_manager
        self.model = None
        # request threads share the interpreter with the micro-batch
        # thread from here on: it keeps to a share of it (pace)
        self.pace = BackgroundShare()

    def _resident_model(self) -> "ALSSpeedModel | None":
        resident = self._serving.get_model()
        if resident is None:
            return None
        if self.model is None or self.model.resident is not resident:
            self.model = ALSSpeedModel(
                resident.features, resident.implicit, self._log_strength,
                self._epsilon, resident=resident)
        return self.model

    # -- consume -------------------------------------------------------------

    def consume_key_message(self, key: str | None, message: str) -> None:
        if self._serving is not None:
            # co-located: the stores are the serving model's and its
            # consumer writes them; a MODEL document still says how
            # strengths are to be read
            if key in (KEY_MODEL, KEY_MODEL_REF):
                pmml = read_pmml_from_update_key_message(key, message)
                if pmml is not None:
                    self._log_strength = pmml_io.get_extension_value(
                        pmml, "logStrength") == "true"
                    self._epsilon = (float(pmml_io.get_extension_value(
                        pmml, "epsilon")) if self._log_strength
                        else float("nan"))
                    self.model = None
            elif key != KEY_UP:
                raise ValueError(f"Bad key: {key}")
            return
        if key == KEY_UP:
            if self.model is None:
                return  # no model to interpret with yet
            parsed = als_common.parse_up_update(message,
                                                self.model.features)
            if parsed is None:
                # malformed, wrong-dimension, or non-finite payload
                # refused at the trust boundary (shared gate:
                # als_common.parse_up_update)
                self.rejected_updates += 1
                return
            kind, id_, vector, _extras = parsed
            if kind == "X":
                self.model.set_user_vector(id_, vector)
            elif kind == "Y":
                self.model.set_item_vector(id_, vector)
            else:
                raise ValueError(f"Bad message: {message}")
            if self._log_rate_limit.test():
                _log.info("%s", self.model)
        elif key in (KEY_MODEL, KEY_MODEL_REF):
            _log.info("Loading new model")
            t_model = time.monotonic()
            model_dir = manifest = None
            if key == KEY_MODEL_REF:
                path, model_dir, manifest = slices.parse_model_ref(message)
                if model_dir is None:
                    model_dir = path.rsplit("/", 1)[0]
            pmml = read_pmml_from_update_key_message(key, message)
            if pmml is None:
                self.rejected_models += 1
                _log.warning("Model document unavailable or corrupt; "
                             "keeping current model")
                return
            try:
                features = int(pmml_io.get_extension_value(pmml, "features"))
            except (TypeError, ValueError):
                self.rejected_models += 1
                _log.warning("Model document failed validation; keeping "
                             "current model")
                return
            implicit = pmml_io.get_extension_value(pmml, "implicit") == "true"
            log_strength = pmml_io.get_extension_value(pmml, "logStrength") == "true"
            epsilon = (float(pmml_io.get_extension_value(pmml, "epsilon"))
                       if log_strength else float("nan"))
            if self.model is None or features != self.model.features:
                _log.warning("No previous model, or # features changed; "
                             "creating new one")
                self.model = ALSSpeedModel(features, implicit, log_strength,
                                           epsilon)
            x_ids = pmml_io.get_extension_content(pmml, "XIDs") or []
            y_ids = pmml_io.get_extension_content(pmml, "YIDs") or []
            self.model.set_expected_ids(x_ids, y_ids)
            self.model.retain_recent_and_user_ids(x_ids)
            self.model.retain_recent_and_item_ids(y_ids)
            if manifest is not None:
                self._load_from_manifest(model_dir, manifest)
                self.model_load_s = round(time.monotonic() - t_model, 6)
            _log.info("Model updated: %s", self.model)
        else:
            raise ValueError(f"Bad key: {key}")

    def _load_from_manifest(self, model_dir: str, manifest: dict) -> None:
        """Bulk-load EVERY slice plus the user artifact (the speed
        model is never sharded); a bad slice fails closed to the
        monolithic artifacts — same contract as the serving manager."""
        try:
            features = self.model.features
            for entry in manifest["slices"]:
                ids, matrix, _ordinals = slices.read_slice(
                    model_dir, entry, features)
                if ids:
                    self.model.bulk_load_items(ids, matrix)
            x_ids, X, _known = slices.read_x_known(
                model_dir, manifest["x"], features)
            if x_ids:
                self.model.bulk_load_users(x_ids, X)
            self.slice_loads += len(manifest["slices"])
        except (slices.SliceIntegrityError, OSError, KeyError, IndexError,
                TypeError, ValueError) as e:
            self.slice_load_fallbacks += 1
            _log.warning("Speed slice load failed (%s); falling back to "
                         "the monolithic artifacts", e)
            from .update import load_features
            from ...common import store
            try:
                y_ids2, Y = load_features(store.join(model_dir, "Y"))
                if y_ids2:
                    self.model.bulk_load_items(y_ids2, Y)
                x_ids2, X2 = load_features(store.join(model_dir, "X"))
                if x_ids2:
                    self.model.bulk_load_users(x_ids2, X2)
            except (OSError, ValueError) as e2:
                _log.error("Monolithic artifact fallback also failed "
                           "(%s); speed model stays below the fold-in "
                           "gate until the store returns", e2)

    # -- produce -------------------------------------------------------------

    def build_updates(self, new_data: Sequence[KeyMessage]) -> Iterable[str]:
        model = self.model if self._serving is None \
            else self._resident_model()
        if model is None or model.get_fraction_loaded() < self.min_model_load_fraction:
            return []
        model.precompute_solvers()

        pace = self.pace
        working = pace.work if pace is not None else contextlib.nullcontext
        with working():
            events = als_common.parse_events(new_data)
        self.events_folded += len(events)
        if self.shard_count > 1:
            from ...cluster.sharding import is_local_item
            owned = [ev for ev in events
                     if is_local_item(ev[1], self.shard_index,
                                      self.shard_count)]
            self.skipped_remote_events += len(events) - len(owned)
            events = owned
        with working():
            agg = als_common.aggregate(events, model.implicit,
                                       model.log_strength, model.epsilon)
        if len(agg.values) == 0:
            return []

        # get() returns None (rather than raising) while the Gramian is
        # still singular — i.e. not enough data yet
        xtx = model.cached_xtx_solver.get(blocking=True)
        yty = model.cached_yty_solver.get(blocking=True)
        if xtx is None or yty is None:
            missing = [c for c, got in ((model.cached_xtx_solver, xtx),
                                        (model.cached_yty_solver, yty))
                       if got is None]
            _log.info("No %s solver yet (%s); skipping %d inputs",
                      " / ".join(c.what for c in missing),
                      "; ".join(c.last_failure or "not computed"
                                for c in missing), len(events))
            return []
        self.micro_batches += 1

        n = len(agg.values)
        k = model.features
        xu = np.full((n, k), np.nan, dtype=np.float32)
        yi = np.full((n, k), np.nan, dtype=np.float32)
        user_names = [agg.user_ids[u] for u in agg.users]
        item_names = [agg.item_ids[i] for i in agg.items]
        for j, (u_name, i_name) in enumerate(zip(user_names, item_names)):
            with working():
                xv = model.get_user_vector(u_name)
                if xv is not None:
                    xu[j] = xv
                yv = model.get_item_vector(i_name)
                if yv is not None:
                    yi[j] = yv

        # both sides, each one batched device solve
        with obstrace.phase("speed.solve", events=n):
            new_xu, x_valid = als_fold_in.fold_in_batch(
                yty, agg.values, xu, yi, model.implicit)
            new_yi, y_valid = als_fold_in.fold_in_batch(
                xtx, agg.values, yi, xu, model.implicit)
        self.last_batch = {"events": len(events),
                           "users": len(agg.user_ids),
                           "items": len(agg.item_ids)}

        out: list[str] = []
        for j in range(n):
            with working():
                if x_valid[j]:
                    out.append(self._to_update_json(
                        "X", user_names[j], new_xu[j], item_names[j]))
                if y_valid[j]:
                    out.append(self._to_update_json(
                        "Y", item_names[j], new_yi[j], user_names[j]))
        return out

    def warm(self, max_events: int) -> int:
        """Compile the fold-in program for every micro-batch size up to
        ``max_events`` (its batch dimension is padded to powers of two),
        so that no micro-batch of a live stream compiles one.  Returns
        how many sizes ran; 0 without a model or a solver."""
        model = self.model if self._serving is None \
            else self._resident_model()
        yty = model.get_yty_solver(blocking=True) if model else None
        if yty is None:
            return 0
        n, m = 0, 8
        while m <= max(8, 1 << max(0, max_events - 1).bit_length()):
            nothing = np.full((m, model.features), np.nan, np.float32)
            als_fold_in.fold_in_batch(yty, np.zeros(m, np.float32),
                                      nothing, nothing, model.implicit)
            m *= 2
            n += 1
        return n

    def _to_update_json(self, matrix: str, id_: str, vector: np.ndarray,
                        other_id: str) -> str:
        # a float32 survives nine significant digits exactly; json's
        # own encoder writes the seventeen of the double it widens to,
        # at twice the time here and 2.5 times it in every consumer's
        # parse (250 numbers a record, under the interpreter lock)
        vec = ",".join(["%.9g" % v for v in
                        np.asarray(vector, np.float32).tolist()])
        head = text_utils.join_json([matrix, id_])[:-1]
        tail = "" if self.no_known_items \
            else "," + text_utils.join_json([other_id])
        return f"{head},[{vec}]{tail}]"
