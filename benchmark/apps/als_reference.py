"""The plain reference for ``/recommend`` and the comparison that decides
``correct``.

The reference is a straightforward float32 ``matmul + top_k`` at
``highest`` matmul precision over the SERVED factors cast to float32,
computed in row blocks so that 20M rows fit beside the store (copied in
spirit from ``chip_smoke.py``'s ``_Reference``; the original is listed
under Open questions in ``PERF.md``).  It shares no code with the
program's kernels: no Pallas, no two-phase selection, no certificate, no
batcher.  The guarantee it holds the program to: the answer is the
``howMany`` best items by dot product over the served factors, known
items never among them.
"""

from __future__ import annotations

from functools import partial

import numpy as np

# Served scores against the float32 reference, relative with an absolute
# floor.  The served factors are bfloat16, every product of two of them
# is exact in float32, and both sides accumulate in float32, so they
# differ by summation order only: some 1e-6 relative over 250 terms.  A
# rescoring in lower precision than the configuration states (bfloat16
# accumulation or a bfloat16 result, relative error 2^-8 = 4e-3; int8
# phase-A values served as scores, worse) misses this by two orders of
# magnitude.
SCORE_RTOL = 2e-5
SCORE_ATOL = 1e-5

_BLOCK = 1 << 17
_MAX_KNOWN = 256


def _ref_top_k():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("k", "block"))
    def top_k(Y, active, X, known, k: int, block: int):
        """Best ``k`` rows of ``Y`` for each row of ``X`` (float32,
        lane-padded like ``Y``), rows in ``known`` (-1 = none) and
        inactive rows excluded."""
        n_blocks = Y.shape[0] // block
        u = X.shape[0]
        rows_u = jnp.arange(u)[:, None]

        def step(carry, b):
            best_s, best_i = carry
            base = b * block
            yb = jax.lax.dynamic_slice_in_dim(Y, base, block) \
                .astype(jnp.float32)
            ab = jax.lax.dynamic_slice_in_dim(active, base, block)
            s = jnp.matmul(X, yb.T, precision=jax.lax.Precision.HIGHEST)
            s = jnp.where(ab[None, :], s, -jnp.inf)
            local = known - base
            inside = (local >= 0) & (local < block)
            s = s.at[rows_u, jnp.where(inside, local, block)].set(
                -jnp.inf, mode="drop")
            cs, ci = jax.lax.top_k(s, k)
            ms, sel = jax.lax.top_k(
                jnp.concatenate([best_s, cs], axis=1), k)
            mi = jnp.take_along_axis(
                jnp.concatenate([best_i, ci + base], axis=1), sel, axis=1)
            return (ms, mi), None

        init = (jnp.full((u, k), -jnp.inf, jnp.float32),
                jnp.zeros((u, k), jnp.int32))
        (s, i), _ = jax.lax.scan(step, init, jnp.arange(n_blocks))
        return s, i

    @jax.jit
    def scores_of(Y, X, rows):
        """Reference scores of given rows (``rows`` is (U, R))."""
        y = jnp.take(Y, rows, axis=0).astype(jnp.float32)
        return jnp.einsum("uf,urf->ur", X, y,
                          precision=jax.lax.Precision.HIGHEST)

    return top_k, scores_of


class Reference:
    """The reference over one served model."""

    def __init__(self, model):
        self.model = model
        self._top_k, self._scores_of = _ref_top_k()
        self.worst_rel_dev = 0.0
        self.checked = 0

    def _arrays(self):
        Y, active = self.model.Y.device_arrays()
        block = _BLOCK
        while Y.shape[0] % block:
            block //= 2
        return Y, active, block

    def _queries(self, user_ids: list[str], width: int,
                 exclude_known: bool):
        import jax.numpy as jnp

        X = np.zeros((len(user_ids), width), np.float32)
        known = np.full((len(user_ids), _MAX_KNOWN), -1, np.int32)
        for j, uid in enumerate(user_ids):
            v = self.model.get_user_vector(uid)
            X[j, :len(v)] = v
            if not exclude_known:
                continue
            rows = [self.model.Y.row_of(i)
                    for i in self.model.get_known_items(uid)]
            rows = [r for r in rows if r is not None]
            if len(rows) > _MAX_KNOWN:
                raise ValueError(f"user {uid} knows {len(rows)} items; "
                                 f"the reference holds {_MAX_KNOWN}")
            known[j, :len(rows)] = rows
        return jnp.asarray(X), jnp.asarray(known)

    def check(self, answers: list[tuple[str, list[dict]]],
              how_many: int, exclude_known: bool = True) -> list[str]:
        """Hold served ``/recommend`` answers (``(user id, [{"id",
        "value"}, ...])``) to the reference; returns what is wrong, one
        line each.  Ids must be the reference's ids in the reference's
        order — except that two items whose reference scores lie within
        the tolerance are a tie, which summation order decides and no two
        correct kernels share — scores within the tolerance, and (unless
        the request said ``considerKnownItems=true``) no known item among
        them."""
        import jax

        if not answers:
            return []
        Y, active, block = self._arrays()
        row_ids = self.model.Y.row_ids()
        problems: list[str] = []
        size = 32 if len(answers) <= 32 else 256
        for start in range(0, len(answers), size):
            chunk = answers[start:start + size]
            users = [u for u, _ in chunk]
            pad = users + [users[-1]] * (size - len(users))
            X, known = self._queries(pad, int(Y.shape[1]), exclude_known)
            ref_s, ref_i = jax.device_get(
                self._top_k(Y, active, X, known, how_many, block))
            served_rows = np.zeros((size, how_many), np.int32)
            for j, (uid, served) in enumerate(chunk):
                for r, got in enumerate(served[:how_many]):
                    row = self.model.Y.row_of(str(got.get("id")))
                    served_rows[j, r] = -1 if row is None else row
            exact = jax.device_get(self._scores_of(
                Y, X, jax.numpy.asarray(np.maximum(served_rows, 0))))
            for j, (uid, served) in enumerate(chunk):
                self.checked += 1
                problems += self._compare(
                    uid, served, how_many, ref_s[j], ref_i[j], exact[j],
                    served_rows[j], row_ids, exclude_known)
        return problems

    def _compare(self, uid, served, how_many, ref_s, ref_i, exact,
                 served_rows, row_ids, exclude_known) -> list[str]:
        what = f"/recommend/{uid}"
        if len(served) != how_many:
            return [f"{what}: {len(served)} results, wanted {how_many}"]
        known = self.model.get_known_items(uid) if exclude_known else ()
        ids = [str(g.get("id")) for g in served]
        out = []
        if len(set(ids)) != len(ids):
            out.append(f"{what}: an item is returned twice")
        for r, got in enumerate(served):
            if ids[r] in known:
                out.append(f"{what} rank {r}: known item {ids[r]} returned")
            if served_rows[r] < 0:
                out.append(f"{what} rank {r}: unknown item {ids[r]}")
                continue
            want = float(exact[r])
            tol = max(SCORE_ATOL, SCORE_RTOL * abs(want))
            try:
                dev = abs(float(got["value"]) - want)
            except (KeyError, TypeError, ValueError):
                out.append(f"{what} rank {r}: no score")
                continue
            self.worst_rel_dev = max(self.worst_rel_dev,
                                     dev / max(abs(want), SCORE_ATOL))
            if not dev <= tol:
                out.append(f"{what} rank {r}: served score "
                           f"{got['value']!r} for {ids[r]}, reference "
                           f"{want!r} (tolerance {tol:.3g})")
            ref_id = row_ids[int(ref_i[r])]
            if ids[r] != ref_id and not abs(want - float(ref_s[r])) <= tol:
                out.append(f"{what} rank {r}: served {ids[r]} ({want!r}), "
                           f"reference {ref_id} ({float(ref_s[r])!r})")
        return out
