"""Tier-1 coverage for the deploy-time AOT warmup (deploy/warmup.py):
its shape planning, its CLI, and the synthetic ratings its training
warm-up draws (app/als/synthetic.py) — all CPU-cheap."""

from __future__ import annotations

import json

import numpy as np
import pytest

from oryx_tpu.app.als.synthetic import synthesize_movielens


def test_warmup_planned_capacity_matches_bulk_load():
    """The AOT warmup's shape planning must predict the EXACT padded
    capacity a real bulk_load produces — a one-row drift would compile
    a ladder no model load ever hits."""
    from oryx_tpu.app.als.feature_vectors import (FeatureVectorStore,
                                                  planned_capacity)

    for n in (1, 16, 17, 40, 1000, 131072, 131073, 400000):
        store = FeatureVectorStore(8)
        store.bulk_load([f"i{j}" for j in range(n)],
                        np.zeros((n, 8), np.float32))
        assert len(store.row_ids()) == planned_capacity(n), n
    # ... and for the REAL serving load path: set_expected_ids
    # pre-sizes via reserve(), so a per-UP-message replay fills the
    # planned (warmed) capacity in place instead of pow2-regrowing
    # through shapes the warmup never compiled
    n = 3000
    store = FeatureVectorStore(8)
    store.reserve(n)
    assert len(store.row_ids()) == planned_capacity(n)
    for j in range(n):
        store.set_vector(f"i{j}", np.ones(8, np.float32))
    assert len(store.row_ids()) == planned_capacity(n)  # no regrow


def test_warmup_cli_reports_compiles(tmp_path):
    """The warmup subcommand compiles a tiny ladder into a fresh cache
    dir and reports per-kernel outcomes (pallas failures on CPU are
    recorded, never fatal)."""
    import os
    import subprocess
    import sys

    conf = tmp_path / "w.conf"
    conf.write_text(
        'oryx { compile-cache-dir = "%s" }\n' % (tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-m", "oryx_tpu", "warmup", "--conf",
         str(conf), "--items", "0.002", "--features", "8",
         "--dtypes", "float32"],
        capture_output=True, text=True,
        # no exported cache placement: the conf's directory decides
        env={k: v for k, v in dict(os.environ, JAX_PLATFORMS="cpu").items()
             if k != "JAX_COMPILATION_CACHE_DIR"})
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["metric"] == "aot_warmup"
    assert report["compiled_count"] > 0 and report["ok"]
    assert report["cache_dir"] == str(tmp_path / "cache")


# -- the ratings the training warm-up compiles its shapes from ---------------

_SHAPE = dict(n_users=400, n_items=300, n_ratings=20_000)


@pytest.mark.parametrize("case", ["seeded", "dense_ids", "n_ratings",
                                  "long_tail"])
def test_synthesize_movielens(case):
    users, items, implicit, explicit, sigma = synthesize_movielens(
        **_SHAPE, seed=11)
    if case == "seeded":
        # same seed -> the same arrays (the warm-up's compiled shapes
        # are a function of them); another seed -> other arrays
        again = synthesize_movielens(**_SHAPE, seed=11)
        for a, b in zip((users, items, implicit, explicit), again):
            np.testing.assert_array_equal(a, b)
        other = synthesize_movielens(**_SHAPE, seed=12)
        assert len(other[0]) != len(users) or not np.array_equal(
            other[1], items)
    elif case == "dense_ids":
        # index space: int32 ids inside [0, n_users) x [0, n_items),
        # one row per (user, item) pair, sorted by it
        assert users.dtype == items.dtype == np.int32
        assert 0 <= users.min() and users.max() < _SHAPE["n_users"]
        assert 0 <= items.min() and items.max() < _SHAPE["n_items"]
        key = users.astype(np.int64) * _SHAPE["n_items"] + items
        assert np.all(np.diff(key) > 0)
    elif case == "n_ratings":
        # every drawn interaction is kept: duplicates fold into the
        # implicit strength, explicit stars are half-steps in 0.5..5
        assert implicit.sum() == _SHAPE["n_ratings"]
        assert implicit.min() >= 1 and len(users) == len(implicit)
        assert len(explicit) == len(users) and sigma == 0.5
        assert explicit.min() >= 0.5 and explicit.max() <= 5.0
        assert np.all(explicit * 2 == np.round(explicit * 2))
    else:
        # the long-tailed item popularity it promises: the top tenth
        # of the items draw four times their even share of the
        # interactions, the bottom half under a fifth of them
        pop = np.sort(np.bincount(items, weights=implicit,
                                  minlength=_SHAPE["n_items"]))[::-1]
        share = np.cumsum(pop) / pop.sum()
        assert share[_SHAPE["n_items"] // 10 - 1] > 0.4
        assert 1.0 - share[_SHAPE["n_items"] // 2 - 1] < 0.2
