"""Pruned serving (ISSUE 36): the step-list builds of the two-phase scan
— ``lax.scan`` as the CPU runs it, ``pallas`` in interpret mode — and
the exact scan over a window's candidates, through ``top_n_batch``,
against the plain reference of the benchmark
(``benchmark/apps/als_lsh_reference.py``: buckets, Hamming ball,
candidates, top-N, the marginal-bit rule); the reference's own teeth;
the spans and counters; and the tiny LSH cell rehearsed through the
benchmark's command."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.apps.als_lsh_reference import BIT_MARGIN, LshReference
from benchmark.apps.als_reference import Reference
from oryx_tpu.app.als import lsh as lsh_mod
from oryx_tpu.app.als import serving_model as sm
from oryx_tpu.app.als.serving_model import ALSServingModel
from oryx_tpu.obs import trace as obstrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FEATURES, ITEMS, USERS, TILE = 12, 5000, 40, 128


def _build(monkeypatch, build: str, seed=0, dtype="float32",
           items=ITEMS) -> ALSServingModel:
    """A model at sample-rate 0.3 over ``items`` standard-normal items,
    256 regions of one 128-row step each, users u0.. with a few known
    items; ``build`` says which phase A its windows run."""
    monkeypatch.setattr(sm, "_PA_TILE", TILE)
    monkeypatch.setattr(sm, "_PALLAS_STATE", {})
    monkeypatch.setattr(sm, "_PALLAS_ERRORS", {})
    if build == "pallas":
        real = sm._batch_top_n_twophase_pallas
        monkeypatch.setattr(
            sm, "_batch_top_n_twophase_pallas",
            lambda *a, **kw: real(*a, **kw, interpret=True))
    rng = np.random.default_rng(seed)
    model = ALSServingModel(FEATURES, True, sample_rate=0.3, dtype=dtype)
    model.bulk_load_items(
        [f"i{j}" for j in range(items)],
        rng.standard_normal((items, FEATURES)).astype(np.float32))
    model.bulk_load_users(
        [f"u{j}" for j in range(USERS)],
        rng.standard_normal((USERS, FEATURES)).astype(np.float32))
    for j in range(USERS):
        model.add_known_items(
            f"u{j}", {f"i{int(i)}" for i in rng.integers(0, items, j % 7)})
    return model


def _serve(model, users, how_many=10, exclude_known=True):
    """``top_n_batch`` for ``users`` as the endpoint calls it, in the
    form the benchmark's reference takes."""
    X = np.stack([model.get_user_vector(u) for u in users])
    exclude = [model.get_known_items(u) if exclude_known else set()
               for u in users]
    out = model.top_n_batch(how_many, X, exclude)
    return [(u, [{"id": i, "value": s} for i, s in rows])
            for u, rows in zip(users, out)]


def _ran(model, build: str) -> None:
    """The windows so far ran the build the test is about."""
    kinds = {key[-1] for key, state in sm._PALLAS_STATE.items()
             if state == "ok" and key[4]}
    assert kinds == {build}, (kinds, sm._PALLAS_ERRORS)


def _with_bucket_of(model, user: str, bucket: int) -> None:
    """Reflect ``user``'s vector across hyperplanes until it hashes to
    ``bucket`` (the hyperplanes are orthonormal: a reflection flips one
    bit and leaves the others)."""
    v = model.get_user_vector(user)
    H = model.lsh.hyperplanes
    for bit in range(model.lsh.num_hashes):
        if ((int(model.lsh.bucket_of(v[None])[0]) ^ bucket) >> bit) & 1:
            v = v - 2 * (v @ H[bit]) * H[bit]
    model.set_user_vector(user, v)
    assert int(model.lsh.bucket_of(
        model.get_user_vector(user)[None])[0]) == bucket


CASES = ["one_caller", "window_of_three", "one_ball", "disjoint_balls",
         "padded_32", "radius_0", "radius_all", "empty_bucket",
         "more_than_a_bucket_holds", "known_considered"]


@pytest.mark.parametrize("build", ["scan", "pallas"])
@pytest.mark.parametrize("case", CASES)
def test_pruned_answers_equal_the_plain_reference(case, build, monkeypatch):
    model = _build(monkeypatch, build, seed=CASES.index(case))
    hashes, radius, how_many, exclude_known = 8, 2, 10, True
    users = ["u3"]
    buckets = {1: 37}            # real rows -> buckets in their union
    if case == "window_of_three":
        users = ["u1", "u8", "u20"]
        buckets = None
    elif case == "one_ball":
        # two rows of one bucket: the union is one ball, 37 buckets
        _with_bucket_of(model, "u5", 0b10110100)
        _with_bucket_of(model, "u6", 0b10110100)
        users = ["u5", "u6"]
        buckets = {2: 37}
    elif case == "disjoint_balls":
        # opposite corners: no bucket within 2 bits of both
        _with_bucket_of(model, "u5", 0b00000000)
        _with_bucket_of(model, "u6", 0b11111111)
        users = ["u5", "u6"]
        buckets = {2: 74}
    elif case == "padded_32":
        users = [f"u{j}" for j in range(9)]      # a 32-wide window
        buckets = None
    elif case == "radius_0":
        model.lsh.max_bits_differing = radius = 0
        how_many = 5
        buckets = {1: 1}
    elif case == "radius_all":
        # every bucket a candidate: the model no longer prunes, scans
        # its laid-out store exactly, and the EXACT reference holds
        model.lsh.max_bits_differing = radius = hashes
        assert not model._lsh_active()
    elif case == "empty_bucket":
        target = int(model.lsh.bucket_of(
            model.get_user_vector("u3")[None])[0])
        table, step, _ = model.Y.partition_layout()
        row_ids = model.Y.row_ids()
        for s in np.flatnonzero(table == target ^ 1):   # one bit away
            for id_ in row_ids[s * step:(s + 1) * step]:
                if id_ is not None:
                    model.Y.remove(id_)
    elif case == "more_than_a_bucket_holds":
        # radius 0 and a fetch wider than the one bucket's live rows:
        # the answer is every candidate there is, in order
        model.lsh.max_bits_differing = radius = 0
        how_many = 40
        buckets = {1: 1}
    elif case == "known_considered":
        users, exclude_known = ["u6", "u13"], False
        buckets = None
    before = model.lsh_windows
    answers = _serve(model, users, how_many, exclude_known)
    if case == "radius_all":
        assert model.lsh_windows == before
        assert Reference(model).check(answers, how_many) == []
        return
    reference = LshReference(model, hashes, radius)
    assert reference.check(answers, how_many, exclude_known) == []
    assert reference.checked == len(users)
    assert model.twophase_fallbacks == 0
    assert model.lsh_windows == before + 1
    if case == "more_than_a_bucket_holds":
        (_, served), = answers
        assert 0 < len(served) < how_many     # one bucket holds ~20 rows
    else:
        assert all(len(served) == how_many for _, served in answers)
        _ran(model, build)
    if case in ("radius_0", "more_than_a_bucket_holds"):
        bucket = int(model.lsh.bucket_of(
            model.get_user_vector(users[0])[None])[0])
        table, step, _ = model.Y.partition_layout()
        assert all(table[model.Y.row_of(g["id"]) // step] == bucket
                   for _, served in answers for g in served)
    if buckets is not None:
        # the plan's own numbers: the union's buckets, one step each
        # here, and the live rows in them
        (n_real, n_buckets), = buckets.items()
        assert len(users) == n_real
        assert model.lsh_streamed_rows == n_buckets * TILE
        assert 0 < model.lsh_candidate_rows <= model.lsh_streamed_rows


@pytest.mark.parametrize("build", ["scan", "pallas"])
def test_one_count_of_requests_feeds_the_plan_and_phase_b(build,
                                                          monkeypatch):
    """PR 38: a window has ONE ``n_real``, an argument of its program
    (``Pruning`` carries none): ``_visit_plan`` unions the balls of that
    many rows and phase B gathers and rescores for that many.  Three
    users on an [8]: the plan's buckets are their union's, the five
    rows behind them come back -inf with their certificates passed, and
    nothing falls to the exact scan."""
    assert "n_real" not in sm.Pruning._fields
    model = _build(monkeypatch, build, seed=33)
    name = "_batch_top_n_twophase_kernel" if build == "scan" \
        else "_batch_top_n_twophase_pallas"
    real = getattr(sm, name)
    seen = []

    def spy(*args, **kw):
        out = real(*args, **kw)
        n_real = args[4 if build == "scan" else 5]
        seen.append((n_real.dtype, int(n_real), jax.device_get(out)))
        return out

    monkeypatch.setattr(sm, name, spy)
    users = ["u2", "u9", "u11"]
    answers = _serve(model, users)
    assert LshReference(model, 8, 2).check(answers, 10) == []
    _ran(model, build)
    (kind, n_real, (ts, ti, cert, stats)), = seen
    assert (kind, n_real) == (np.int32, 3)
    assert np.isfinite(ts[:3, 0]).all() and np.isneginf(ts[3:]).all()
    assert (ti[3:] == 0).all()
    assert cert.all() and model.twophase_fallbacks == 0
    # the union of THREE balls, not of eight rows' (five zero rows
    # would all hash to one bucket and add its ball)
    balls = {int(b) for u in users for b in np.flatnonzero(
        [bin(int(model.lsh.bucket_of(model.get_user_vector(u)[None])[0])
             ^ c).count("1") <= 2 for c in range(256)])}
    assert int(stats[1]) == len(balls)
    m = model.metrics()
    assert (m["phase_b_rows"], m["phase_b_window_rows"]) == (3, 8)


@pytest.mark.parametrize("k", [16, 32])
@pytest.mark.parametrize("b, n_real", [(8, 1), (8, 2), (8, 7), (8, 8),
                                       (32, 9)])
def test_a_pruned_windows_requests_answer_as_the_whole_window_did(
        b, n_real, k, monkeypatch):
    """The pruned program's phase B, a request an iteration, against
    the whole-window arithmetic (every row gathered, multiplied and
    sorted in one piece, as before PR 38) over the same plan: maxima in
    visit order mapped back through ``steps``, -inf wherever a step is
    no candidate of the row, and at k = 32 fewer candidate blocks (37)
    than the 64 the selection takes.  Scores, rows and certificates of
    the requests bit for bit; the padding -inf, row 0 and passed."""
    model = _build(monkeypatch, "scan", seed=37)
    vecs, active = model.Y.device_arrays()
    prune = model._pruning(active)
    ksel = sm._block_ksel(k, int(vecs.shape[0]), 128)
    assert ksel == 2 * k
    Q = np.zeros((b, FEATURES), np.float32)
    Q[:n_real] = np.stack([model.get_user_vector(f"u{j}")
                           for j in range(n_real)])

    def program(whole: bool):
        # traced afresh: which rows phase B rescores is read then
        monkeypatch.setattr(sm, "_rescores_requests",
                            (lambda width: False) if whole
                            else (lambda width: width < 128))
        fn = jax.jit(sm._batch_top_n_twophase_kernel.__wrapped__,
                     static_argnames=("k", "chunk", "bs", "ksel",
                                      "max_bits"))
        return jax.device_get(fn(vecs, jnp.asarray(Q), active, prune,
                                 np.int32(n_real), k, 0, 128, ksel, 2))

    ts, ti, cert, stats = program(False)
    want_s, want_i, want_c, want_stats = program(True)
    np.testing.assert_array_equal(stats, want_stats)
    r = slice(0, n_real)
    np.testing.assert_array_equal(ts[r], want_s[r])
    np.testing.assert_array_equal(ti[r], want_i[r])
    np.testing.assert_array_equal(cert[r], want_c[r])
    assert cert.all() and np.isfinite(ts[r, 0]).all()
    if k == 32:
        # 37 candidate blocks of ~20 live rows each hold the fetch, and
        # the blocks the selection filled up with gave no row
        assert np.isfinite(ts[r]).all()
        table, step, _ = model.Y.partition_layout()
        for row in range(n_real):
            bucket = int(model.lsh.bucket_of(Q[row][None])[0])
            assert all(bin(int(table[i // step]) ^ bucket).count("1") <= 2
                       for i in ti[row])
    assert np.isneginf(ts[n_real:]).all() and (ti[n_real:] == 0).all()


@pytest.mark.parametrize("build", ["scan", "pallas"])
def test_only_a_requests_failed_certificate_sends_the_window_on(
        build, monkeypatch):
    """The first request's certificate fails: the window is answered by
    the exact scan over its candidates and ONE row is counted.  Nothing
    behind the three requests can fail (the test above), so the same
    drain unsabotaged counts none."""
    model = _build(monkeypatch, build, seed=31)
    name = "_batch_top_n_twophase_kernel" if build == "scan" \
        else "_batch_top_n_twophase_pallas"
    real = getattr(sm, name)
    exact = []
    real_exact = sm._batch_top_n_pruned_exact_kernel
    monkeypatch.setattr(
        sm, "_batch_top_n_pruned_exact_kernel",
        lambda *a, **kw: exact.append(1) or real_exact(*a, **kw))
    users = ["u2", "u9", "u11"]
    want = _serve(model, users)
    assert model.twophase_fallbacks == 0 and exact == []

    def sabotaged(*args, **kw):
        ts, ti, cert, stats = real(*args, **kw)
        return ts, ti, cert.at[0].set(False), stats

    monkeypatch.setattr(sm, name, sabotaged)
    answers = _serve(model, users)
    assert model.twophase_fallbacks == 1 and exact == [1]
    assert LshReference(model, 8, 2).check(answers, 10) == []
    assert [[g["id"] for g in a[1]] for a in answers] \
        == [[g["id"] for g in a[1]] for a in want]


@pytest.mark.parametrize("build", ["scan", "pallas"])
def test_a_failed_certificate_is_answered_within_the_candidates(
        build, monkeypatch):
    model = _build(monkeypatch, build, seed=31)
    name = "_batch_top_n_twophase_kernel" if build == "scan" \
        else "_batch_top_n_twophase_pallas"
    real = getattr(sm, name)

    def sabotaged(*args, **kw):
        ts, ti, cert, stats = real(*args, **kw)
        return ts * 0 - 1.0, ti * 0, cert & False, stats   # rubbish, failed

    monkeypatch.setattr(sm, name, sabotaged)
    exact = []
    real_exact = sm._batch_top_n_pruned_exact_kernel
    monkeypatch.setattr(
        sm, "_batch_top_n_pruned_exact_kernel",
        lambda *a, **kw: exact.append(1) or real_exact(*a, **kw))
    users = ["u2", "u9", "u11"]
    answers = _serve(model, users)
    assert model.twophase_fallbacks == 8 and exact == [1]
    assert LshReference(model, 8, 2).check(answers, 10) == []
    # ... and not by the exact scan of the whole store
    unpruned = _serve_exact(model, users)
    assert [a[1] for a in answers] != [a[1] for a in unpruned]


def _serve_exact(model, users, how_many=10):
    X = np.stack([model.get_user_vector(u) for u in users])
    out = model.top_n_batch(how_many, X,
                            [model.get_known_items(u) for u in users],
                            use_lsh=False)
    return [(u, [{"id": i, "value": s} for i, s in rows])
            for u, rows in zip(users, out)]


def test_use_lsh_false_scans_the_laid_out_store_exactly(monkeypatch):
    model = _build(monkeypatch, "scan", seed=32)
    users = ["u4", "u5"]
    assert Reference(model).check(_serve_exact(model, users), 10) == []
    assert model.lsh_windows == 0


def test_the_single_request_path_holds_to_the_same_candidates(monkeypatch):
    model = _build(monkeypatch, "scan", seed=33)
    for u in ("u7", "u12"):
        rows = model.top_n(10, user_vector=model.get_user_vector(u),
                           exclude=model.get_known_items(u))
        answer = [(u, [{"id": i, "value": s} for i, s in rows])]
        (_, batched), = _serve(model, [u])
        assert [g["id"] for g in batched] == [i for i, _ in rows]
        np.testing.assert_allclose([g["value"] for g in batched],
                                   [s for _, s in rows], rtol=1e-5)
        assert LshReference(model, 8, 2).check(answer, 10) == []


def test_a_bfloat16_store_under_lsh(monkeypatch):
    model = _build(monkeypatch, "scan", seed=34, dtype="bfloat16")
    reference = LshReference(model, 8, 2)
    assert reference.layout_problems() == []
    assert reference.check(_serve(model, ["u1", "u2", "u3"]), 10) == []
    assert reference.worst_rel_dev < 2e-5


def test_updates_keep_the_served_candidates_right(monkeypatch):
    """Writes between drains: new items, items that cross a hyperplane
    (a row move) and removals reach the device in place, the steps'
    table follows, and the answers stay the reference's."""
    model = _build(monkeypatch, "scan", seed=35)
    users = ["u1", "u2"]
    _serve(model, users)
    rng = np.random.default_rng(36)
    H = model.lsh.hyperplanes
    for j in range(0, 300, 3):
        v = model.get_item_vector(f"i{j}")
        model.set_item_vector(f"i{j}", v - 2 * (v @ H[j % 8]) * H[j % 8])
    for j in range(200):
        model.set_item_vector(
            f"new{j}", 2 * rng.standard_normal(FEATURES).astype(np.float32))
    for j in range(1, 300, 3):
        model.Y.remove(f"i{j}")
    assert model.lsh_row_moves == 100
    reference = LshReference(model, 8, 2)
    assert reference.check(_serve(model, users), 10) == []
    assert reference.layout_problems() == []
    assert model.Y.device_syncs == 2          # the load, one in-place sync


# -- the marginal-bit rule, and the reference's teeth ---------------------------

def _on_hyperplane(model, rng, bit: int) -> np.ndarray:
    """A vector whose product with hyperplane ``bit`` is rounding."""
    H = model.lsh.hyperplanes
    v = 3 * rng.standard_normal(FEATURES).astype(np.float32)
    v = v - (v @ H[bit]) * H[bit]
    assert abs(float(v @ H[bit])) < 1e-6 * np.linalg.norm(v)
    return v.astype(np.float32)


def test_an_item_on_a_hyperplane_may_lie_either_side(monkeypatch):
    model = _build(monkeypatch, "scan", seed=41)
    rng = np.random.default_rng(42)
    v = _on_hyperplane(model, rng, 5)
    model.set_item_vector("edge", v)
    reference = LshReference(model, 8, 2)
    assert reference.layout_problems() == []
    assert reference.marginal_rows >= 1
    # wherever the program put it, the other side is as good: move the
    # row by hand to the region one bit away
    table, step, _ = model.Y.partition_layout()
    here = int(table[model.Y.row_of("edge") // step])
    other = int(np.flatnonzero(table == here ^ (1 << 5))[0])
    _move_row_by_hand(model, "edge", other)
    assert LshReference(model, 8, 2).layout_problems() == []
    # a user whose query reaches it through that bit only is answered
    # either way, and the check holds the answer to the union
    model.set_user_vector("edgy", 5 * v)
    assert LshReference(model, 8, 2).check(_serve(model, ["edgy"]), 10) == []
    # the same row in a region TWO bits away is a layout fault
    wrong = int(np.flatnonzero(table == here ^ 0b11)[0])
    _move_row_by_hand(model, "edge", wrong)
    (problem,) = LshReference(model, 8, 2).layout_problems()
    assert "1 live rows" in problem


def _move_row_by_hand(model, id_: str, step_index: int) -> None:
    """Put ``id_``'s row into a free row of another step, behind the
    store's back (what a wrong hash would have done)."""
    Y = model.Y
    part = Y._part
    with Y._lock.write():
        old = Y._id_to_row[id_]
        new = next(r for r in range(step_index * part.step,
                                    (step_index + 1) * part.step)
                   if not Y._active[r])
        part.free[part.bucket_of_row(new)].remove(new)
        Y._host[new], Y._active[new] = Y._host[old], True
        Y._row_to_id[new], Y._id_to_row[id_] = id_, new
        Y._release(old)
        Y._dirty.add(new)
        Y._mutations += 1


def test_a_query_on_a_hyperplane_is_held_to_the_union_of_its_balls(
        monkeypatch):
    model = _build(monkeypatch, "scan", seed=43)
    rng = np.random.default_rng(44)
    model.set_user_vector("qedge", _on_hyperplane(model, rng, 2))
    reference = LshReference(model, 8, 2)
    assert reference.check(_serve(model, ["qedge"]), 10) == []
    assert reference.met_marginal_bit == 1
    assert reference.recall() is not None and 0 < reference.recall() <= 1


def test_the_reference_finds_what_is_wrong(monkeypatch):
    model = _build(monkeypatch, "scan", seed=45)
    reference = LshReference(model, 8, 2)
    (user, served), = _serve(model, ["u9"])
    assert reference.check([(user, served)], 10) == []
    # an item outside the ball, however good its score
    outside = _serve_exact(model, ["u9"])[0][1]
    stranger = next(g for g in outside
                    if g["id"] not in {s["id"] for s in served})
    bad = [stranger] + served[:9]
    assert any("bits from the query's bucket" in p
               for p in reference.check([(user, bad)], 10))
    # the best candidate missing
    assert any("is missing" in p
               for p in reference.check([(user, served[1:])], 9))
    # a known item returned, a wrong score, a wrong order
    known = served[0]["id"]
    model.add_known_items(user, [known])
    assert any("known item" in p
               for p in reference.check([(user, served)], 10))
    assert reference.check(_serve(model, [user]), 10) == []
    off = [dict(served[1], value=served[1]["value"] * 1.001)] + served[2:]
    assert any("served score" in p
               for p in reference.check([(user, off)], 9))
    swapped = [served[2], served[1]] + served[3:]
    assert any("after a lower score" in p
               for p in reference.check([(user, swapped)], 9))


def test_a_one_pass_bfloat16_bucket_product_fails_the_margin(monkeypatch):
    """The margin is float32 rounding, not a percent of the catalog: a
    program that hashes at the MXU's default precision (operands rounded
    to bfloat16, emulated here) puts rows where the reference does not."""
    def one_pass(vectors, hyperplanes, num_hashes: int):
        lo = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        signs = jnp.matmul(lo(vectors), lo(hyperplanes).T) > 0.0
        weights = jnp.asarray([1 << i for i in range(num_hashes)], jnp.int32)
        return jnp.sum(signs.astype(jnp.int32) * weights[None, :], axis=1)

    monkeypatch.setattr(lsh_mod, "_bucket_kernel", one_pass)
    model = _build(monkeypatch, "scan", seed=46, items=40_000)
    reference = LshReference(model, 8, 2)
    (problem,) = reference.layout_problems()
    assert "live rows lie in the region of a bucket that differs" in problem
    assert reference.worst_flipped_bit > BIT_MARGIN
    # the program's own kernel: nothing beyond the margin, at any size
    monkeypatch.undo()
    model = _build(monkeypatch, "scan", seed=46, items=40_000)
    reference = LshReference(model, 8, 2)
    assert reference.layout_problems() == []
    assert reference.worst_flipped_bit <= BIT_MARGIN


def test_every_bucket_product_is_computed_at_highest():
    text = lsh_mod._bucket_kernel.lower(
        jnp.zeros((8, 16), jnp.bfloat16), jnp.zeros((4, 16), jnp.float32),
        num_hashes=4).as_text()
    assert text.count("HIGHEST") == 2 and "DEFAULT" not in text


# -- spans, counters, warm-up ----------------------------------------------------

def test_the_scan_phase_carries_what_the_window_streamed(monkeypatch):
    model = _build(monkeypatch, "scan", seed=51)
    _with_bucket_of(model, "u5", 0b00000000)
    _with_bucket_of(model, "u6", 0b11111111)
    X = np.stack([model.get_user_vector(u) for u in ("u5", "u6")])
    with obstrace.DrainPhases() as rec:
        model.top_n_batch(10, X)
    scan = next(p for p in rec._phases if p[0] == "serving.scan")[3]
    live = len(model.Y)
    assert scan["lsh_buckets"] == 74 and scan["lsh_steps"] == 74
    assert scan["lsh_candidate_rows"] == model.lsh_candidate_rows
    assert 0 < scan["lsh_candidate_rows"] < 74 * TILE
    assert scan["lsh_streamed_share"] == round(100 * 74 * TILE / live, 3)
    m = model.metrics()["lsh"]
    assert (m["windows"], m["streamed_rows"]) == (1, 74 * TILE)
    # an exact drain of the same model carries none of them
    with obstrace.DrainPhases() as rec:
        model.top_n_batch(10, X, use_lsh=False)
    assert not any(k.startswith("lsh_") for p in rec._phases for k in p[3])


def test_a_warmed_model_under_lsh_compiles_nothing_for_any_window(
        monkeypatch):
    """A pruned window's grid and loops run to a bound the device
    computes: one program a (window, k), however many steps it visits."""
    model = _build(monkeypatch, "scan", seed=52)
    model.warm_serving_kernels(how_many=10, max_batch=8)
    compiles = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: compiles.append(event)
        if event == "/jax/compilation_cache/compile_requests_use_cache"
        else None)
    route = model.metrics()["kernel_route"]
    assert route["use_lsh"] is True and route["chosen"] == "scan"
    before = len(compiles)
    rng = np.random.default_rng(53)
    for n in (1, 2, 5, 8):            # 37 to ~200 buckets in the union
        model.top_n_batch(
            10, rng.standard_normal((n, FEATURES)).astype(np.float32))
    assert len(compiles) == before
    assert model.lsh_windows >= 4


# -- the cell, rehearsed ---------------------------------------------------------

def test_the_lsh_cell_rehearsed_through_the_benchmarks_command():
    """``benchmark/run.py --rehearse`` on the tiny LSH configuration:
    the whole command on the CPU backend, the pruned reference deciding
    ``correct``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearse", "--manifest",
         "benchmark/tests/rehearsal_lsh_manifest.json", "--workload",
         "tiny-lsh.two-callers", "--seed", "3600000017", "--seconds", "2",
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    detail, line = (json.loads(text)
                    for text in done.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    app = detail["detail"]["app"]
    assert app["partitioning"]["hashes"] == 8
    assert app["partitioning"]["radius"] == 2
    assert app["kernel_route"]["use_lsh"] is True
    assert app["checked"]["in_all"] >= 32
    assert 0 < app["lsh"]["recall_at_10"] < 1
    assert app["lsh"]["largest_bit_program_and_reference_differ_on"] \
        <= BIT_MARGIN
    assert app["counters"]["lsh_windows"] > 0
    assert detail["detail"]["compile_cache"]["compiled_in_window"] == 0
    assert detail["detail"]["problems"] == []
