"""Phase B over the rows that are requests (PR 38): in a window narrower
than a lane tile the gather and the exact rescoring of the selected
blocks run for the first ``n_real`` rows only, a traced scalar the
program reads, and the zero rows the dispatch pads a window with cost
nothing there.  On the CPU backend's ``lax.scan`` build: a request
answers as the whole-window arithmetic answered it, bit for bit; the
padding returns -inf, row 0 and a passed certificate; a (window, k)
stays ONE compiled program whatever it holds; a request that fails its
certificate is the only row counted; and from 128 rows on phase B is the
program it was."""

import gzip
import inspect
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest, trace_reduce  # noqa: E402
from benchmark.observe import Observations  # noqa: E402
from oryx_tpu.app.als import serving_model as sm  # noqa: E402
from oryx_tpu.app.als.serving_model import ALSServingModel  # noqa: E402
from oryx_tpu.deploy import warmup  # noqa: E402

# 64 features: from there on the CPU backend sums a lone row's products
# (a matrix-vector product) in another order than a batch of them, so a
# loop that multiplied a batch of ONE would show here
F, BS, N = 64, 8, 8192


def _whole_window_phase_b(Y, Qc, active, M, k: int, bs: int, ksel: int):
    """The reference: phase B as it was before PR 38, every row of the
    window gathered, multiplied and sorted in one piece
    (``_phase_b_rows`` at commit 1b27177, the exact scan's half)."""
    b = Qc.shape[0]
    m_sel, bi = jax.lax.approx_max_k(M, ksel,
                                     recall_target=sm._APPROX_RECALL)
    m_rest = M.at[jnp.arange(b)[:, None], bi].set(-jnp.inf).max(-1)
    Yg = jnp.take(Y.reshape(-1, bs, Y.shape[1]), bi, axis=0)
    scores = jnp.einsum("bf,bkcf->bkc", Qc, Yg,
                        preferred_element_type=jnp.float32,
                        precision=sm._score_precision(Y)
                        ).reshape(b, ksel * bs)
    ok = jnp.take(active.reshape(-1, bs), bi, axis=0)
    scores = jnp.where(ok.reshape(b, ksel * bs), scores, -jnp.inf)
    ts, ti = jax.lax.top_k(scores, k)
    rows = (bi[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(
                b, ksel * bs)
    idx = jnp.take_along_axis(rows, ti, axis=1)
    m_guard = jnp.where(jnp.isfinite(m_rest),
                        m_rest + jnp.abs(m_rest) * 1e-4, m_rest)
    return ts, idx, ts[:, k - 1] >= m_guard


@partial(jax.jit, static_argnames=("k", "ksel"))
def _reference(Y, Q, active, k: int, ksel: int):
    Qc = sm._q_cast(Q, Y)
    M = sm._scan_block_maxima(Qc, Y, active, 1024, BS)
    return _whole_window_phase_b(Y, Qc, active, M, k, BS, ksel)


def _case(b: int, dtype, seed: int = 41, n: int = N):
    rng = np.random.default_rng(seed)
    Y = jnp.asarray(rng.standard_normal((n, F)).astype(np.float32)
                    ).astype(dtype)
    Q = jnp.asarray(rng.standard_normal((b, F)).astype(np.float32))
    act = np.ones(n, bool)
    act[::9] = False
    return Y, Q, jnp.asarray(act)


def _padded(Q, n_real: int):
    """What the dispatch hands over: zeros behind the requests."""
    return Q.at[n_real:].set(0.0)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("k", [16, 32, 256])
@pytest.mark.parametrize("b, n_real", [(8, 1), (8, 2), (8, 7), (8, 8),
                                       (32, 1), (32, 2), (32, 31),
                                       (32, 32)])
def test_a_request_answers_as_the_whole_window_arithmetic_did(
        b, n_real, k, dtype):
    """Scores, row ids and certificates of the requests are the
    whole-window arithmetic's bit for bit (same blocks selected, same
    products reduced, same guard), and the exact scan's; a padding row
    returns -inf, row 0 and a certificate that passes."""
    assert sm._rescores_requests(b)
    Y, Q, active = _case(b, jnp.dtype(dtype))
    Q = _padded(Q, n_real)
    ksel = sm._block_ksel(k, N, BS)
    assert ksel == max(32, 2 * k) and sm._twophase_admits(k, ksel, Y, BS)
    ts, ti, cert = jax.device_get(sm._batch_top_n_twophase_kernel(
        Y, Q, active, None, np.int32(n_real), k, 1024, BS, ksel))
    want_s, want_i, want_c = jax.device_get(
        _reference(Y, Q, active, k, ksel))
    r = slice(0, n_real)
    np.testing.assert_array_equal(ts[r], want_s[r])
    np.testing.assert_array_equal(ti[r], want_i[r])
    np.testing.assert_array_equal(cert[r], want_c[r])
    assert cert[r].all() and np.isfinite(ts[r]).all()
    ex_s, ex_i = jax.device_get(sm._batch_top_n_chunked_kernel(
        Y, Q, active, k, 1024))
    np.testing.assert_array_equal(ts[r], ex_s[r])
    for row in range(n_real):
        # ties may swap places between the two sorts, never the set
        assert set(ti[row].tolist()) == set(ex_i[row].tolist()) \
            or ts[row, -1] == ts[row, -2]
    # the rows behind the requests: nothing scored, nothing to decode,
    # nothing that could fail
    assert np.isneginf(ts[n_real:]).all()
    assert (ti[n_real:] == 0).all() and cert[n_real:].all()


def test_a_row_behind_the_requests_cannot_fail_the_window():
    """Nobody looks at a row past ``n_real``, whatever it holds: a row
    that WOULD fail its certificate (33 copies of one item in 33 blocks
    where 32 are selected: its k-th score equals the best unselected
    maximum, and the margin fails it) passes there, and fails as the
    whole-window arithmetic failed it once it is a request."""
    b, k = 8, 16
    ksel = sm._block_ksel(k, N, BS)
    Y, Q, _ = _case(b, jnp.float32, seed=43)
    active = jnp.ones((N,), bool)
    y = np.asarray(Y).copy()
    y[:, 0] = np.abs(y[:, 0]) * 0.1      # nobody else comes near
    for block in range(ksel + 1):
        y[block * BS + 3] = 0.0
        y[block * BS + 3, 0] = 40.0
    Y = jnp.asarray(y)
    # the two requests look away from the copies, row 2 aims at them
    Q = Q.at[:, 0].set(0.0).at[2].set(0.0).at[2, 0].set(1.0)
    assert not jax.device_get(_reference(Y, Q, active, k, ksel))[2][2]
    prog = sm._batch_top_n_twophase_kernel
    ts, ti, cert = jax.device_get(prog(
        Y, Q, active, None, np.int32(2), k, 1024, BS, ksel))
    assert cert.all() and np.isneginf(ts[2:]).all() and (ti[2:] == 0).all()
    ts, ti, cert = jax.device_get(prog(
        Y, Q, active, None, np.int32(3), k, 1024, BS, ksel))
    assert cert.tolist() == [True, True, False] + [True] * 5
    assert (ts[2] == 40.0).all()


def test_one_compiled_program_whatever_the_window_holds():
    """``n_real`` is an argument, never a shape: the (window, k) program
    called with 1, 2 and 8 requests is ONE entry in its jit cache."""
    Y, Q, active = _case(8, jnp.float32, seed=47, n=N + 1024)
    fn = sm._batch_top_n_twophase_kernel
    before = fn._cache_size()
    for n_real in (1, 2, 8):
        ts, _, cert = jax.device_get(fn(
            Y, _padded(Q, n_real), active, None, np.int32(n_real), 16,
            1024, BS, 32))
        assert np.isfinite(ts[:n_real]).all() and cert.all()
        assert np.isneginf(ts[n_real:]).all()
    assert fn._cache_size() - before == 1


def _prims(jaxpr) -> list[str]:
    """Every primitive of a jaxpr, its sub-jaxprs' included, in order."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_prims(inner))
    return out


@pytest.mark.parametrize("b, k, groups", [(128, 16, 1), (256, 16, 1),
                                          (256, 64, 2)])
def test_from_128_rows_on_phase_b_is_the_program_it_was(b, k, groups,
                                                        monkeypatch):
    """The route measurement times 256-wide programs: their phase B
    takes no notice of ``n_real`` and lowers to the whole-window
    arithmetic's operations, one for one (in equal row groups under
    ``lax.map`` where one gather would pass the budget), with no loop
    over requests."""
    assert not sm._rescores_requests(b)
    Y, Q, active = _case(b, jnp.float32, seed=49)
    ksel = sm._block_ksel(k, N, BS)
    monkeypatch.setattr(sm, "_PHASE_B_GATHER_BYTES",
                        (b // groups) * ksel * BS * F * 4)
    assert sm._phase_b_group_rows(b, ksel, BS, F * 4) == b // groups
    M = jnp.zeros((b, N // BS), jnp.float32)
    Qc = sm._q_cast(Q, Y)

    def now(n):
        return sm._phase_b(Y, Qc, active, M, n, k, BS, ksel)

    def before(n):
        return sm._map_row_groups(
            lambda q, m: _whole_window_phase_b(Y, q, active, m, k, BS, ksel),
            b // groups, Qc, M)

    got, want = (_prims(jax.make_jaxpr(f)(np.int32(2)).jaxpr)
                 for f in (now, before))
    assert sorted(got) == sorted(want)
    # (lax.map is a scan; a loop to a bound the device reads a while)
    assert got.count("while") == 0
    assert got.count("scan") == (0 if groups == 1 else 1)
    # ... where a narrow window's holds the loop over its requests
    narrow = _prims(jax.make_jaxpr(
        lambda n: sm._phase_b(Y, Qc[:8], active, M[:8], n, k, BS, ksel))(
            np.int32(2)).jaxpr)
    assert narrow.count("while") == 1
    # and the answers do not depend on the count
    prog = sm._batch_top_n_twophase_kernel
    a = jax.device_get(prog(Y, Q, active, None, np.int32(b), k, 1024, BS,
                            ksel))
    c = jax.device_get(prog(Y, Q, active, None, np.int32(2), k, 1024, BS,
                            ksel))
    for x, y in zip(a, c):
        np.testing.assert_array_equal(x, y)


@pytest.fixture
def ladder(monkeypatch):
    """The streaming two-phase branch at toy scale (tests/test_als.py)."""
    monkeypatch.setattr(sm, "_FLAT_SCORES_LIMIT", 1)
    monkeypatch.setattr(sm, "_MAX_CHUNK_ROWS", 512)
    monkeypatch.setattr(sm, "_BLOCK_ROWS", 64)
    monkeypatch.setattr(sm, "_BLOCK_KSEL", 8)


def _model(Y):
    model = ALSServingModel(Y.shape[1], implicit=True)
    model.Y.bulk_load([f"i{j}" for j in range(len(Y))], Y)
    return model


def test_the_dispatch_hands_every_window_its_own_count(ladder, monkeypatch):
    """258 requests are a [256, 8] drain: the first window holds 256
    requests, the tail 2, and each program is told so, by a scalar that
    is on the device already; ``/metrics`` counts the rows phase B
    rescored (every row of the wide window, the tail's two) against the
    rows of the windows."""
    rng = np.random.default_rng(3)
    model = _model(rng.standard_normal((4096, 8)).astype(np.float32))
    seen = []
    real = sm._batch_top_n_twophase_kernel

    def spy(Y, Q, active, prune, n_real, *args, **kw):
        assert isinstance(n_real, jax.Array) and n_real.shape == ()
        seen.append((int(Q.shape[0]), n_real.dtype, int(n_real)))
        return real(Y, Q, active, prune, n_real, *args, **kw)

    monkeypatch.setattr(sm, "_batch_top_n_twophase_kernel", spy)
    X = rng.standard_normal((258, 8)).astype(np.float32)
    got = model.top_n_batch(5, X)
    assert seen == [(256, np.int32, 256), (8, np.int32, 2)]
    # placed once a count, whatever the number of drains
    assert sorted(model._counts) == [2, 256]
    assert model.twophase_fallbacks == 0
    m = model.metrics()
    assert (m["phase_b_rows"], m["phase_b_window_rows"]) == (258, 264)
    # ... and the answers are the flat kernel's
    vecs, active = model.Y.device_arrays()
    _, ti = jax.device_get(sm._batch_top_n_kernel(
        vecs, jnp.asarray(X), active, 8))
    ids = model.Y.row_ids()
    assert [[i for i, _ in row] for row in got] \
        == [[ids[j] for j in r[:5]] for r in ti.tolist()]
    model.top_n_batch(5, X[:3])
    m = model.metrics()
    assert (m["phase_b_rows"], m["phase_b_window_rows"]) == (261, 272)
    assert sorted(model._counts) == [2, 3, 256]


def test_a_request_that_fails_is_the_only_row_counted(ladder):
    """Seventeen copies of one item in seventeen blocks, and a request
    that aims at it: its k-th score EQUALS the best maximum among the
    blocks phase B left unselected (16 of them fit the selection), the
    certificate's margin fails it, and the window goes to the exact
    scan.  The other request passes; the six rows of padding behind the
    two neither fail nor count."""
    rng = np.random.default_rng(9)
    Y = rng.standard_normal((2048, 8)).astype(np.float32)
    star = np.zeros(8, np.float32)
    star[0] = 40.0
    Y[:, 0] = np.abs(Y[:, 0]) * 0.1      # nobody else comes near
    for block in range(17):
        Y[block * 64 + 3] = star
    model = _model(Y)
    X = np.zeros((2, 8), np.float32)
    X[0, 0] = 1.0                        # aims at the copies
    X[1, 1:] = rng.standard_normal(7)
    assert sm._block_ksel(8, 2048, 64) == 16
    got = model.top_n_batch(5, X)
    assert model.twophase_fallbacks == 1
    # the exact scan's answer: five of the copies, whichever
    assert [s for _, s in got[0]] == [40.0] * 5
    assert {i for i, _ in got[0]} <= {f"i{b * 64 + 3}" for b in range(17)}
    vecs, active = model.Y.device_arrays()
    _, ti = jax.device_get(sm._batch_top_n_kernel(
        vecs, jnp.asarray(np.concatenate([X, np.zeros((6, 8), np.float32)])),
        active, 8))
    ids = model.Y.row_ids()
    assert [i for i, _ in got[1]] == [ids[j] for j in ti[1, :5].tolist()]
    m = model.metrics()
    assert (m["phase_b_rows"], m["phase_b_window_rows"]) == (2, 8)


def test_warm_serving_kernels_warms_the_signature_the_drain_calls(ladder):
    """After ``warm_serving_kernels`` a drain of 1, 2 or 8 requests
    compiles nothing and uploads no count: the warm-up called the
    program the drain calls, and placed the counts it hands over."""
    rng = np.random.default_rng(5)
    model = _model(rng.standard_normal((4096 + 512, 8)).astype(np.float32))
    model.warm_serving_kernels(5, max_batch=8)
    fn = sm._batch_top_n_twophase_kernel
    warmed, placed = fn._cache_size(), set(model._counts)
    # every count a narrow window can hold is on the device already
    assert set(range(1, 33)) <= placed
    for n in (1, 2, 8):
        assert len(model.top_n_batch(5, rng.standard_normal(
            (n, 8)).astype(np.float32))) == n
    assert fn._cache_size() == warmed and set(model._counts) == placed
    assert model.twophase_fallbacks == 0


@pytest.mark.parametrize("sample_rate", [1.0, 0.3])
def test_the_aot_warm_up_compiles_the_count_as_the_drain_passes_it(
        sample_rate, monkeypatch):
    """``deploy/warmup.py`` lowers every two-phase build (and the pruned
    exact scan) from avals: the count of requests is a strong int32
    scalar there, which is what ``ALSServingModel._count`` places on
    the device, so the drain finds the compiled program."""
    lowered = []
    monkeypatch.setattr(
        warmup, "_compile",
        lambda report, name, fn, *args, **static: lowered.append(
            (name, fn, args)))
    warmup.warm_serving_shapes(50, 600_000, "bfloat16", sample_rate,
                               {"compiled": [], "failed": []})
    counted = [(name, args[list(inspect.signature(
        fn.__wrapped__).parameters).index("n_real")])
        for name, fn, args in lowered
        if "n_real" in inspect.signature(fn.__wrapped__).parameters]
    builds = {name.split(": ")[1].split(" ")[0] for name, _ in counted}
    assert {"twophase_scan", "pallas"} <= builds
    assert ("pruned_exact" in builds) == (sample_rate < 1)
    if sample_rate == 1.0:
        assert {"pallas_fold", "pallas_i8", "pallas_i8_fold"} <= builds
    model = ALSServingModel(8, implicit=True)
    placed = model._count(2)
    for name, aval in counted:
        assert (aval.shape, aval.dtype) == ((), jnp.int32), name
        assert (placed.shape, placed.dtype, placed.weak_type) \
            == ((), aval.dtype, False)


def test_the_slowest_program_reader_on_the_recorded_trace():
    """``kernel.twophase_slowest_ms`` through its layer file, on the
    kept piece of a real v5e trace (benchmark/tests/
    test_phase_b_metrics.py holds the reader's other cases): four
    compiled two-phase programs, the slowest 15.993 ms an execution."""
    with gzip.open(os.path.join(
            ROOT, "benchmark", "testdata",
            "v5e_als250_two_callers.json.gz"), "rt") as fh:
        trace = trace_reduce.reduce_trace(json.load(fh))
    metrics = {m.name: m for m in manifest.resolve(
        ROOT, "BENCHMARK.json", "als250-20m-lsh03.two-callers").per_layer}
    obs = Observations(spans=[], counters_start={}, counters_end={},
                       batch_sizes=[], trace=trace, store={}, peaks=None)
    assert metrics["kernel.twophase_slowest_ms"].read(obs) \
        == pytest.approx(15.993198)
    # the parent's spans carry no ``phase_b_row_share``: nothing to read
    assert metrics["kernel.phase_b_row_share"].read(obs) is None
