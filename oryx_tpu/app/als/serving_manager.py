"""ALS serving model manager: replays the update topic into the
serving model.

Reference: app/oryx-app-serving/src/main/java/com/cloudera/oryx/app/
serving/als/model/ALSServingModelManager.java:45-160 — UP handling with
known-items (:70-105), solver pre-trigger at load fraction (:96-103),
MODEL/MODEL-REF handling with retain logic (:107-130),
loadRescorerProviders (:142-160).
"""

from __future__ import annotations

import gc
import logging
import time

import numpy as np

from ...api.serving import AbstractServingModelManager
from ...cluster.membership import KEY_HEARTBEAT
from ...cluster.sharding import is_local_item, parse_shard_spec
from ...common import pmml as pmml_io
from ...common import store
from ...common.config import Config
from ...common.lang import BackgroundShare, RateLimitCheck
from ...kafka.api import KEY_MODEL, KEY_MODEL_REF, KEY_UP
from ..pmml_utils import read_pmml_from_update_key_message
from . import common as als_common
from . import ivf
from . import slices
from .rescorer import load_rescorer_providers
from .serving_model import ALSServingModel

_log = logging.getLogger(__name__)

__all__ = ["ALSServingModelManager"]


# an UP record published at most this long ago is part of a live stream
_LIVE_MS = 10_000


def _is_live(headers: dict | None) -> bool:
    """Whether a record's ``ts`` header (its publish time, epoch ms; the
    speed layer stamps it) is recent; False without one."""
    try:
        return time.time() * 1000 - int(headers["ts"]) < _LIVE_MS
    except (TypeError, KeyError, ValueError):
        return False


class ALSServingModelManager(AbstractServingModelManager):

    def __init__(self, config: Config):
        super().__init__(config)
        self.model: ALSServingModel | None = None
        self._triggered_solver = False
        self.rescorer_provider = load_rescorer_providers(
            config.get_optional_string("oryx.als.rescorer-provider-class"))
        self.sample_rate = config.get_double("oryx.als.sample-rate")
        self.factor_dtype = config.get_string("oryx.als.factor-dtype")
        # P4/P5 scale-out: shard the item matrix over a device mesh
        # (oryx.serving.api.item-shards; 1 = single-chip scan)
        self.item_shards = config.get_int("oryx.serving.api.item-shards")
        self.int8_selection = config.get_string(
            "oryx.serving.api.int8-selection")
        if self.int8_selection not in ("auto", "true", "false"):
            raise ValueError("int8-selection must be auto/true/false")
        self.fold_scan = config.get_string("oryx.serving.api.fold-scan")
        if self.fold_scan not in ("auto", "true", "false"):
            raise ValueError("fold-scan must be auto/true/false")
        # IVF ANN serving path (oryx.als.ann.*, ISSUE 18): parsed and
        # validated at boot like every other serving knob
        self.ann_config = ivf.AnnConfig.from_config(config)
        if self.item_shards < 1 or (self.item_shards
                                    & (self.item_shards - 1)):
            raise ValueError("item-shards must be a power of two >= 1")
        # fail at boot, not hours later on the consumer thread when the
        # first MODEL message finally constructs the serving model
        from .feature_vectors import resolve_dtype
        resolve_dtype(self.factor_dtype)
        self.min_model_load_fraction = config.get_double(
            "oryx.serving.min-model-load-fraction")
        if not 0.0 < self.sample_rate <= 1.0:
            raise ValueError("sample-rate must be in (0,1]")
        self._log_rate_limit = RateLimitCheck(60.0)
        # integrity counters: how many poison payloads this consumer
        # refused instead of absorbing into the serving model
        self.rejected_updates = 0
        self.rejected_models = 0
        # UP records applied to the model
        self.updates_applied = 0
        # headers of the record being consumed (consume), else None
        self._headers: dict | None = None
        # the consumer thread's share of the interpreter once the model
        # serves (consume)
        self._pace = BackgroundShare()
        # -- serving-cluster state (oryx_tpu/cluster/) -------------------
        # catalog shard this replica materializes: Y vectors whose id
        # hashes elsewhere are skipped (the user store and known-items
        # stay FULL — they are needed for local exclusion and are tiny
        # next to the item matrix).  "0/1" = the whole catalog, i.e.
        # plain single-node serving.
        spec = (config.get_optional_string("oryx.cluster.shard")
                if config.get_bool("oryx.cluster.enabled") else None)
        self.shard_index, self.shard_count = parse_shard_spec(spec or "0/1")
        # accepted MODEL/MODEL-REF documents since replay offset 0 —
        # the replica's model GENERATION, identical across replicas
        # (the update topic is totally ordered), carried in heartbeats
        # so the router never routes to a replica serving older state
        self.generation = 0
        # item id -> first-appearance index in the Y update stream: the
        # cluster's canonical tie-break ordinal (cluster/merge.py),
        # identical on every replica for the same topic replay.
        # Counts EVERY Y id seen, including ones this shard skips.
        self.item_ordinals: dict[str, int] = {}
        # next ordinal to assign.  NOT len(item_ordinals): a
        # slice-loaded replica holds ordinals for its LOCAL slices only
        # (slices carry the global index of each row), so the counter
        # must advance from the manifest's TOTAL item count — every
        # replica then assigns the same ordinal to the same
        # post-publish UP id regardless of which slices it loaded.
        self._ordinal_next = 0
        # Y vectors skipped as non-local (observability)
        self.skipped_remote_items = 0
        # -- sharded model distribution (slices.py) ----------------------
        # slices bulk-loaded, artifact bytes read, and fallbacks to the
        # monolithic artifacts (missing/corrupt slice, incompatible
        # ring) — surfaced as gauges on /metrics by the serving layer
        self.slice_loads = 0
        self.slice_load_fallbacks = 0
        self.model_slice_bytes = 0
        # seconds from MODEL(-REF) receipt to a servable model: the
        # slice path stamps it when the bulk load finishes; the replay
        # path stamps it when the UP stream crosses the load-fraction
        # gate.  THE number sharded distribution exists to shrink.
        self.model_load_s = 0.0
        self._model_received_at: float | None = None
        # sum of the owned slices' manifest Gramians: /shard/yty
        # answers from it without a device scan until a Y write lands
        self._slice_yty: "object | None" = None
        # -- IVF ANN index (ivf.py) --------------------------------------
        # device bytes pinned by the current generation's IVF mirror
        # and how many generations failed CLOSED to the exact kernel
        # (corrupt artifact / failed build / failed recall measurement)
        # — surfaced as gauges on /metrics by the serving layer
        self.ann_index_bytes = 0
        self.ann_index_fallbacks = 0
        # per-generation published-index state collected during the
        # slice load, consumed by _maybe_build_ann
        self._ann_centroid_entry: dict | None = None
        self._ann_cells_by_id: dict[str, int] = {}
        self._ann_artifacts_broken = False

    def get_model(self) -> ALSServingModel | None:
        return self.model

    def consume(self, updates) -> None:
        """As the base class, with each record's headers at hand: an UP
        record's ``batch`` header (the speed layer's micro-batch number)
        tags the item rows it writes, and comes back from the store with
        the device sync that makes them servable."""
        for km in updates:
            self._headers = km.headers
            try:
                if km.key == KEY_UP and self._triggered_solver \
                        and _is_live(km.headers):
                    # a live model taking a live stream: request
                    # threads share the interpreter with this one.  A
                    # load (before the trigger) and a backlog (a replay,
                    # or a consumer that fell behind) run flat out
                    with self._pace.work():
                        self.consume_key_message(km.key, km.message)
                else:
                    self.consume_key_message(km.key, km.message)
            finally:
                self._headers = None

    def consume_key_message(self, key: str | None, message: str) -> None:
        if key == KEY_UP:
            model = self.model
            if model is None:
                return  # no model to interpret with yet
            parsed = als_common.parse_up_update(message, model.features)
            if parsed is None:
                # malformed, wrong-dimension, or non-finite payload
                # refused at the trust boundary (shared gate:
                # als_common.parse_up_update)
                self.rejected_updates += 1
                return
            kind, id_, vector, extras = parsed
            if kind == "X":
                model.set_user_vector(id_, vector)
                if extras is not None:
                    model.add_known_items(id_, [str(i) for i in extras])
            elif kind == "Y":
                # ordinal BEFORE the shard filter: the canonical
                # tie-break must agree across replicas that each skip
                # different ids.  The counter advances for EVERY Y
                # record — not every new id — because a slice-loaded
                # replica holds only its LOCAL slices' ordinals and
                # cannot tell a remote MANIFEST item from a genuinely
                # new one: advancing per record keeps the counter (and
                # therefore every new id's ordinal) identical on every
                # replica of the totally ordered topic, whatever subset
                # each loaded.  setdefault keeps an already-known id's
                # ordinal stable; the skipped slots are harmless gaps
                # (ordinals only need a shared total order).
                self.item_ordinals.setdefault(id_, self._ordinal_next)
                self._ordinal_next += 1
                if is_local_item(id_, self.shard_index, self.shard_count):
                    model.set_item_vector(
                        id_, vector,
                        tag=(self._headers or {}).get("batch"))
                    # a live Y write outdates the manifest's partial
                    # Gramian: /shard/yty scans again until next load
                    self._slice_yty = None
                else:
                    self.skipped_remote_items += 1
            else:
                raise ValueError(f"Bad message: {message}")
            self.updates_applied += 1
            # load-fraction trigger OUTSIDE the log rate limiter: a
            # bulk replay that finishes inside one 60 s window must
            # not serve a minute of live traffic without solvers or a
            # measured kernel route (the `not triggered` bool keeps
            # the post-trigger per-UP cost at one attribute read)
            if (not self._triggered_solver
                    and model.get_fraction_loaded()
                    >= self.min_model_load_fraction):
                self._triggered_solver = True
                gc.freeze()  # the loaded model leaves the collector's sight (below)
                # the replay path's load clock: MODEL receipt -> the UP
                # stream crossing the serving gate (the slice path
                # stamps its own, much earlier, moment)
                if self._model_received_at is not None:
                    self.model_load_s = round(
                        time.monotonic() - self._model_received_at, 6)
                    self._model_received_at = None
                model.precompute_solvers()
                # replay-loaded factors: build the IVF index + measure
                # the recall certificate before routing, so the route
                # below is measured against the chain ANN may join
                self._maybe_build_ann(None)
                # with the factors loaded, time each eligible kernel
                # path for the live shape so serving routes by
                # measured cost (re-measures only if the store's
                # padded capacity changed since)
                model.refresh_route()
            if self._log_rate_limit.test():
                _log.info("%s", model)
        elif key in (KEY_MODEL, KEY_MODEL_REF):
            _log.info("Loading new model")
            t_model = time.monotonic()
            model_dir = manifest = None
            if key == KEY_MODEL_REF:
                # manifest-carrying envelope (slices.py): the record
                # names the per-slice artifacts this replica may
                # bulk-load instead of replaying a full UP stream
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
                # parseable XML that is not a factored-model document
                # (e.g. recovered from a partially corrupt artifact)
                self.rejected_models += 1
                _log.warning("Model document failed validation; keeping "
                             "current model")
                return
            implicit = pmml_io.get_extension_value(pmml, "implicit") == "true"
            if self.model is None or features != self.model.features:
                _log.warning("No previous model, or # features changed; "
                             "creating new one")
                # a REPLACEMENT model starts un-triggered: the solver
                # precompute + kernel-route measurement must re-fire at
                # ITS load-fraction threshold, not stay latched off by
                # the previous model's trigger
                self._triggered_solver = False
                self.model = ALSServingModel(
                    features, implicit, self.sample_rate,
                    self.rescorer_provider, dtype=self.factor_dtype,
                    item_shards=self.item_shards,
                    int8_selection=self.int8_selection,
                    fold_scan=self.fold_scan,
                    ann_config=self.ann_config
                    if self.ann_config.enabled else None)
            _log.info("Updating model")
            x_ids = set(pmml_io.get_extension_content(pmml, "XIDs") or [])
            y_ids = set(pmml_io.get_extension_content(pmml, "YIDs") or [])
            # sharded replica: expected-ID accounting and the Y retain
            # run over the LOCAL slice only (fraction-loaded gates on
            # what this shard will actually materialize); known-items
            # retain keeps the GLOBAL id universe — exclusion works by
            # id and must cover items other shards hold
            local_y = [i for i in y_ids
                       if is_local_item(i, self.shard_index,
                                        self.shard_count)] \
                if self.shard_count > 1 else list(y_ids)
            self.model.set_expected_ids(list(x_ids), local_y)
            self.model.retain_recent_and_known_items(list(x_ids), list(y_ids))
            self.model.retain_recent_and_user_ids(list(x_ids))
            self.model.retain_recent_and_item_ids(local_y)
            self.generation += 1
            self._model_received_at = t_model
            # a NEW generation outdates any held manifest Gramian
            # immediately (the retains above already pruned rows); a
            # successful slice load below sets the fresh one
            self._slice_yty = None
            # reset the previous generation's published-index state
            # before any load path repopulates it
            self._ann_centroid_entry = None
            self._ann_cells_by_id = {}
            self._ann_artifacts_broken = False
            if manifest is not None:
                # sharded distribution: bulk-load exactly this shard's
                # slices (O(catalog/N)); a bad slice fails closed to
                # the monolithic artifacts — ready either way
                self._load_from_manifest(model_dir, manifest)
            # IVF index build INSIDE the load clock: `model_load_s`
            # covers it (the index is part of being servable at the
            # advertised latency), and it must precede refresh_route so
            # the measured route includes the "ivf" kind
            self._maybe_build_ann(model_dir)
            if (self._model_received_at is not None
                    and self.model.get_fraction_loaded()
                    >= self.min_model_load_fraction):
                # the artifacts alone crossed the serving gate (slice
                # or fallback load): the replica is SERVABLE now —
                # stamp the load clock before the route measurement
                # and solver precompute below, which are warmup the
                # replay path also runs outside its clock
                self.model_load_s = round(time.monotonic() - t_model, 6)
                self._model_received_at = None
            # hot-swap: the new generation may have regrown the padded
            # store — refresh the measured-cost kernel route for the
            # new shape (no-op while capacity and LSH config match)
            self.model.refresh_route()
            if (not self._triggered_solver
                    and self.model.get_fraction_loaded()
                    >= self.min_model_load_fraction):
                # no UP flood follows to fire the load-fraction
                # trigger, so the solvers precompute here
                self._triggered_solver = True
                # a loaded model is long-lived: out of the collector's
                # sight, or a full collection under traffic walks its
                # 20M ids with the interpreter lock held (seconds;
                # ServingLayer.start does the same for what was built
                # before it)
                gc.freeze()
                self.model.precompute_solvers()
            _log.info("Model updated: %s", self.model)
        elif key == KEY_HEARTBEAT:
            # cluster control-plane traffic on the shared update topic;
            # the layers' consume threads already filter it, this guard
            # covers direct manager drives (tests, embedding)
            return
        else:
            raise ValueError(f"Bad key: {key}")

    # -- sharded model distribution (slices.py) ------------------------------

    def _load_from_manifest(self, model_dir: str, manifest: dict) -> None:
        """Bulk-load this shard's slices + the user artifact; any
        integrity failure fails closed to :meth:`_load_full_artifacts`
        with the ``slice_load_fallbacks`` counter — a corrupt slice
        costs the O(catalog) load, never readiness."""
        try:
            ring = int(manifest["ring"])
            owned = slices.owned_slices(ring, self.shard_index,
                                        self.shard_count)
            if owned is None:
                raise slices.SliceIntegrityError(
                    f"slice ring {ring} incompatible with shard count "
                    f"{self.shard_count} (pick a ring the shard count "
                    f"divides)")
            features = self.model.features
            total_bytes = 0
            gramian = np.zeros((features, features), dtype=np.float64)
            # gramians live only in the STORE manifest (k*k floats per
            # slice would blow the topic's max message size); absence
            # just means /shard/yty scans instead
            full = slices.read_manifest(model_dir)
            grams = (full or {}).get("gramians")
            entries = {int(e["slice"]): e for e in manifest["slices"]}
            self._ann_centroid_entry = manifest.get("ann")
            for s in owned:
                entry = entries[s]
                ids, matrix, ordinals = slices.read_slice(
                    model_dir, entry, features)
                if ids:
                    self.model.bulk_load_items(ids, matrix)
                    self.item_ordinals.update(zip(ids, ordinals))
                total_bytes += int(entry.get("bytes", 0))
                if grams is not None:
                    gramian += np.asarray(grams[s], dtype=np.float64)
                self._collect_slice_ann(model_dir, entry, ids)
            x_ids, X, known = slices.read_x_known(
                model_dir, manifest["x"], features)
            if x_ids:
                self.model.bulk_load_users(x_ids, X)
                for uid, items in zip(x_ids, known):
                    if items:
                        self.model.add_known_items(uid, items)
            total_bytes += int(manifest["x"].get("bytes", 0))
            self._ordinal_next = max(self._ordinal_next,
                                     int(manifest["items"]))
            self.slice_loads += len(owned)
            self.model_slice_bytes = total_bytes
            self._slice_yty = gramian if grams is not None else None
            _log.info(
                "Slice-loaded %d/%d slices (%d items, %d users, %d "
                "bytes) for shard %d/%d", len(owned), ring,
                len(self.model.Y), len(self.model.X), total_bytes,
                self.shard_index, self.shard_count)
        except (slices.SliceIntegrityError, OSError, KeyError, IndexError,
                TypeError, ValueError) as e:
            self.slice_load_fallbacks += 1
            self._slice_yty = None
            # a failed slice load discredits the whole manifest, the
            # published index artifacts with it: the ANN build (if
            # enabled) trains locally over whatever the fallback loads
            self._ann_centroid_entry = None
            self._ann_cells_by_id = {}
            _log.warning("Slice load failed (%s); falling back to the "
                         "monolithic artifacts", e)
            self._load_full_artifacts(model_dir)

    def _load_full_artifacts(self, model_dir: str) -> None:
        """The fail-closed path: read the monolithic ``Y``/``X``
        artifacts the publisher still writes, filter to this shard,
        and assign ordinals by artifact position — exactly the state a
        full-stream replay would have built (the artifact order IS the
        stream order)."""
        from .update import load_features
        try:
            y_ids, Y = load_features(store.join(model_dir, "Y"))
            local = [j for j, iid in enumerate(y_ids)
                     if is_local_item(iid, self.shard_index,
                                      self.shard_count)]
            if local:
                self.model.bulk_load_items(
                    [y_ids[j] for j in local], Y[local])
            self.skipped_remote_items += len(y_ids) - len(local)
            for j, iid in enumerate(y_ids):
                self.item_ordinals.setdefault(iid, j)
            self._ordinal_next = max(self._ordinal_next, len(y_ids))
            x_ids, X = load_features(store.join(model_dir, "X"))
            if x_ids:
                self.model.bulk_load_users(x_ids, X)
            _log.info("Fallback-loaded monolithic artifacts: %d local "
                      "items, %d users", len(local), len(x_ids))
        except (OSError, ValueError) as e:
            # store unreachable: the replica stays below the serving
            # gate and the router routes around it — log, don't die
            _log.error("Monolithic artifact fallback also failed (%s); "
                       "replica will not reach ready until the store "
                       "returns", e)

    # -- IVF ANN index (ivf.py, ISSUE 18) ------------------------------------

    def _collect_slice_ann(self, model_dir: str, entry: dict,
                           ids: list[str]) -> None:
        """Read one owned slice's published cell assignments.  A
        corrupt/missing index artifact (chaos point
        ``ann-index-corrupt``) never fails the SLICE load — the
        factors are intact — but marks the generation's published
        index broken so ``_maybe_build_ann`` fails CLOSED to the exact
        kernel."""
        aent = entry.get("ann")
        if aent is None or not self.ann_config.enabled \
                or self._ann_artifacts_broken:
            return
        try:
            cells = ivf.read_slice_cells(model_dir, aent)
            self._ann_cells_by_id.update(zip(ids, cells))
        except ivf.AnnIndexError as e:
            self._ann_artifacts_broken = True
            _log.warning("ANN index artifact unusable (%s); this "
                         "generation will serve on the exact kernel", e)

    def _maybe_build_ann(self, model_dir: str | None) -> None:
        """Build the generation's IVF index over this replica's owned
        rows and measure its recall certificate against the exact
        kernel (``ivf.measure_recall``) — BEFORE routing, so
        ``refresh_route`` measures the chain ANN may join.  Published
        artifacts (centroids + per-slice cells) skip the local k-means
        training; any failure anywhere fails CLOSED to the exact
        kernel with ``ann_index_fallbacks`` — ANN is an optimization,
        never a readiness gate."""
        cfg = self.ann_config
        model = self.model
        if not cfg.enabled or model is None or model._item_shards > 1 \
                or len(model.Y) == 0:
            return
        try:
            if self._ann_artifacts_broken:
                raise ivf.AnnIndexError(
                    "published index artifacts unreadable")
            cells = None
            if self._ann_centroid_entry is not None \
                    and model_dir is not None:
                centroids = ivf.read_centroids(
                    model_dir, self._ann_centroid_entry)
                cells = self._published_cells()
            else:
                yv, ya, _ids = model.Y.host_arrays()
                centroids = ivf.train_generation_centroids(
                    yv[ya][:, :model.features], cfg)
            state = ivf.AnnState(cfg, centroids, cells=cells)
            model.attach_ann(state)
            vecs, active, version = model.Y.device_arrays_versioned()
            mirror = model._cached_ivf(vecs, active, version)
            state.recall = ivf.measure_recall(model, mirror, cfg)
            self.ann_index_bytes = mirror.index_bytes
            if state.recall < cfg.min_recall:
                _log.warning(
                    "IVF recall certificate FAILED for generation %d: "
                    "recall@%d %.4f < min-recall %.2f — serving stays "
                    "on the exact kernel", self.generation,
                    cfg.recall_at, state.recall, cfg.min_recall)
            else:
                _log.info(
                    "IVF index ready for generation %d: %d cells, "
                    "nprobe %d, recall@%d %.4f, %d bytes",
                    self.generation, int(state.centroids.shape[0]),
                    cfg.nprobe, cfg.recall_at, state.recall,
                    mirror.index_bytes)
        except Exception as e:  # noqa: BLE001 — fail closed to exact
            self.ann_index_fallbacks += 1
            self.ann_index_bytes = 0
            model.attach_ann(None)
            _log.warning("IVF ANN index build failed (%s); generation "
                         "%d serves on the exact kernel", e,
                         self.generation)

    def _published_cells(self) -> "np.ndarray | None":
        """Published per-slice cell assignments re-aligned to the
        store's row slots.  Partial coverage (a row the artifacts do
        not name) returns None — the mirror build assigns on device
        instead, which is always correct."""
        by_id = self._ann_cells_by_id
        if not by_id:
            return None
        row_ids = self.model.Y.row_ids()
        cells = np.zeros(len(row_ids), dtype=np.int32)
        for i, rid in enumerate(row_ids):
            if rid is None:
                continue
            c = by_id.get(rid)
            if c is None:
                return None
            cells[i] = c
        return cells

    def partial_yty(self) -> "np.ndarray | None":
        """This shard's Gramian from the manifest's per-slice partials
        — lets ``/shard/yty`` answer without a device scan — or None
        when no fresh manifest Gramian is held (replay-loaded model, a
        Y write since load, or a manifest without Gramians)."""
        g = self._slice_yty
        return None if g is None else np.asarray(g, dtype=np.float64)
