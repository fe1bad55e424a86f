"""The plain reference for ``/recommend`` under LSH (``oryx.als.sample-rate``
< 1), and the comparison that decides ``correct`` in an ``als_lsh`` cell.

The semantics it holds the program to are the reference implementation's
(LocalitySensitiveHash.java, ALSServingModel.java:265-280): an item's
BUCKET is the sign bits of the products of its factor vector with the
deployment's hyperplanes; a request's CANDIDATES are the items whose
bucket differs from the bucket of the request's query vector in at most
``radius`` bits; the answer is the ``howMany`` best candidates by dot
product, known items left out.

Everything here is plain ``jax.numpy`` / NumPy in float32 at ``highest``
matmul precision over the SERVED factors, in row blocks so that 20M x 250
fit beside the server (as ``als_reference.py`` computes its own).  The
hyperplanes are read from the model as data: they are the deployment's
weights, drawn from the seed.  It shares no code with the program's
pruned path: no kernel, no layout, no step list, no batching, nothing of
``serving_model.py`` or ``lsh.py``.  Where the program keeps each row is
read (``FeatureVectorStore.partition_layout``) only to hold the layout
itself to the rule, row by row.

THE MARGIN.  One rule needs a stated tolerance: a sign bit whose product
lies within rounding of zero may fall either way in the program.  A
product is p = sum_f y_f h_f over F = 250 terms of exact float32 products
(the served y are bfloat16, the hyperplanes float32) accumulated in
float32: two correct float32 summations differ by at most about
F * 2^-24 * sum|y_f h_f| <= F * 6e-8 * |y||h| = 1.5e-5 |y||h|, and by
about sqrt(F) * 6e-8 = 1e-6 |y||h| in practice.  A ONE-PASS bfloat16
product rounds the hyperplanes (and a float32 query) to bfloat16 first:
an error of about 2^-9 / sqrt(3) a term, 1.1e-3 * sqrt(sum (y_f h_f)^2)
or some 7e-5 |y||h| for a product, the largest of 20M x 8 ten times that.
So a bit is MARGINAL when |p| <= 1e-5 |y||h| (``BIT_MARGIN``): ten times
what float32 rounding does in practice, and a seventh of what one
bfloat16 pass does to a typical product, so that a program that hashes
at the default matmul precision is caught on a few rows in a thousand
(the two readings, measured on the chip, are in PERF.md section 6, PR 36).

The check: every live row lies in the region of a bucket that agrees with
the reference's in every bit that is not marginal; and every answer holds
only items that are candidates under SOME reading of the marginal bits
(the item's and the query's: a query with a marginal bit is held to the
union of its balls), none that is known, each with the reference's score
within ``als_reference``'s tolerance, in descending order, and lacks no
item that is a candidate under EVERY reading and scores above the last
one returned.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from benchmark.apps.als_reference import (_BLOCK, SCORE_ATOL, SCORE_RTOL,
                                          Reference)

# |p| <= BIT_MARGIN * |y| * |h| makes a sign bit marginal (module docstring)
BIT_MARGIN = 1e-5


def _functions():
    import jax
    import jax.numpy as jnp

    highest = jax.lax.Precision.HIGHEST

    def bits_of(V, H, margin):
        """(sign bits, marginal bits, |p| / (|v||h|)) of the rows of
        ``V`` (float32) against the hyperplanes ``H``."""
        p = jnp.matmul(V, H.T, precision=highest)
        scale = jnp.linalg.norm(V, axis=1)[:, None] \
            * jnp.linalg.norm(H, axis=1)[None, :]
        weights = 1 << jnp.arange(H.shape[0], dtype=jnp.int32)
        bits = jnp.sum(jnp.where(p > 0, weights, 0), axis=1)
        marginal = jnp.sum(
            jnp.where(jnp.abs(p) <= margin * scale, weights, 0), axis=1)
        return bits, marginal, jnp.abs(p) / jnp.maximum(scale, 1e-30)

    @partial(jax.jit, static_argnames=("block",))
    def item_bits(Y, active, placed, H, margin, block: int):
        """Every row's (sign bits, marginal bits), and against the
        bucket ``placed`` the program keeps the row under: how many live
        rows differ in a bit that is not marginal, and the largest
        |p| / (|y||h|) of any differing bit (what the margin has to
        cover)."""
        weights = 1 << jnp.arange(H.shape[0], dtype=jnp.int32)

        def step(carry, b):
            wrong, worst = carry
            yb = jax.lax.dynamic_slice_in_dim(Y, b * block, block) \
                .astype(jnp.float32)
            ab = jax.lax.dynamic_slice_in_dim(active, b * block, block)
            pb = jax.lax.dynamic_slice_in_dim(placed, b * block, block)
            bits, marginal, rel = bits_of(yb, H, margin)
            differ = jnp.where(ab, bits ^ pb, 0)
            wrong += jnp.sum((differ & ~marginal) != 0)
            flipped = (differ[:, None] & weights[None, :]) != 0
            worst = jnp.maximum(worst, jnp.max(jnp.where(flipped, rel, 0.0)))
            return (wrong, worst), (bits, marginal)

        (wrong, worst), (bits, marginal) = jax.lax.scan(
            step, (jnp.int32(0), jnp.float32(0)),
            jnp.arange(Y.shape[0] // block))
        return bits.reshape(-1), marginal.reshape(-1), wrong, worst

    @partial(jax.jit, static_argnames=("k", "block", "radius"))
    def sure_top_k(Y, active, bits, marginal, X, qbits, qmarginal, known,
                   k: int, block: int, radius: int):
        """Best ``k`` rows for each row of ``X`` among the rows that are
        candidates under EVERY reading of the marginal bits, rows in
        ``known`` (-1 = none) and inactive rows excluded."""
        u = X.shape[0]
        rows_u = jnp.arange(u)[:, None]

        def step(carry, b):
            best_s, best_i = carry
            base = b * block
            yb = jax.lax.dynamic_slice_in_dim(Y, base, block) \
                .astype(jnp.float32)
            ab = jax.lax.dynamic_slice_in_dim(active, base, block)
            ib = jax.lax.dynamic_slice_in_dim(bits, base, block)
            im = jax.lax.dynamic_slice_in_dim(marginal, base, block)
            s = jnp.matmul(X, yb.T, precision=highest)
            apart = (ib[None, :] ^ qbits[:, None]) | im[None, :] \
                | qmarginal[:, None]
            sure = jax.lax.population_count(apart) <= radius
            s = jnp.where(ab[None, :] & sure, s, -jnp.inf)
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
        (s, i), _ = jax.lax.scan(step, init,
                                 jnp.arange(Y.shape[0] // block))
        return s, i

    @jax.jit
    def query_bits(X, H, margin):
        bits, marginal, _ = bits_of(X, H, margin)
        return bits, marginal

    return item_bits, sure_top_k, query_bits


class LshReference:
    """The reference over one served model under LSH."""

    def __init__(self, model, hashes: int, radius: int):
        self.model = model
        self.hashes, self.radius = int(hashes), int(radius)
        # the exact reference: its queries, its scores, and the exact
        # top-N that recall is read against
        self.exact = Reference(model)
        self._item_bits, self._sure_top_k, self._query_bits = _functions()
        self._bits = None        # (store version, bits, marginal)
        self.checked = 0
        self.worst_rel_dev = 0.0
        # readings that judge nothing
        self.met_marginal_bit = 0      # checked answers that met one
        self.marginal_rows = 0         # live rows with a marginal bit
        self.worst_flipped_bit = 0.0   # largest |p|/(|y||h|) of a bit on
        #                                which program and reference differ
        self.recall_hits = self.recall_of = 0

    # -- the hyperplanes and the items' bits ---------------------------------

    def _hyperplanes(self, width: int):
        import jax.numpy as jnp

        H = np.asarray(self.model.lsh.hyperplanes, np.float32)
        if H.shape[0] != self.hashes:
            raise ValueError(f"the model hashes with {H.shape[0]} "
                             f"hyperplanes, the deployment says "
                             f"{self.hashes}")
        out = np.zeros((H.shape[0], width), np.float32)
        out[:, :H.shape[1]] = H
        return jnp.asarray(out)

    def _arrays(self):
        Y, active, version = self.model.Y.device_arrays_versioned()
        block = _BLOCK
        while Y.shape[0] % block:
            block //= 2
        return Y, active, version, block

    def layout_problems(self) -> list[str]:
        """Hold where the program keeps every live row to the rule (and
        compute the items' bits, once a store version)."""
        import jax.numpy as jnp

        Y, active, version, block = self._arrays()
        table, step, _ = self.model.Y.partition_layout()
        placed = jnp.repeat(jnp.asarray(table[:Y.shape[0] // step]), step)
        bits, marginal, wrong, worst = self._item_bits(
            Y, active, placed, self._hyperplanes(int(Y.shape[1])),
            BIT_MARGIN, block)
        self._bits = (version, bits, marginal)
        self.worst_flipped_bit = max(self.worst_flipped_bit, float(worst))
        self.marginal_rows = int(jnp.sum(active & (marginal != 0)))
        if int(wrong):
            return [f"{int(wrong)} live rows lie in the region of a "
                    "bucket that differs from the reference's in a bit "
                    f"whose product is beyond {BIT_MARGIN:g} |y||h| "
                    f"(largest differing bit at {float(worst):.3g})"]
        return []

    # -- answers ---------------------------------------------------------------

    def check(self, answers: list[tuple[str, list[dict]]],
              how_many: int, exclude_known: bool = True) -> list[str]:
        """Hold served ``/recommend`` answers (``(user id, [{"id",
        "value"}, ...])``) to the reference (module docstring); returns
        what is wrong, one line each."""
        import jax
        import jax.numpy as jnp

        if not answers:
            return []
        Y, active, version, block = self._arrays()
        problems: list[str] = []
        if self._bits is None or self._bits[0] != version:
            problems += self.layout_problems()
        _, bits, marginal = self._bits
        H = self._hyperplanes(int(Y.shape[1]))
        row_ids = self.model.Y.row_ids()
        size = 32 if len(answers) <= 32 else 256
        for start in range(0, len(answers), size):
            chunk = answers[start:start + size]
            users = [u for u, _ in chunk]
            pad = users + [users[-1]] * (size - len(users))
            X, known = self.exact._queries(pad, int(Y.shape[1]),
                                           exclude_known)
            qbits, qmarginal = self._query_bits(X, H, BIT_MARGIN)
            sure_s, sure_i = jax.device_get(self._sure_top_k(
                Y, active, bits, marginal, X, qbits, qmarginal, known,
                how_many, block, self.radius))
            exact_s, exact_i = jax.device_get(self.exact._top_k(
                Y, active, X, known, how_many, block))
            served_rows = np.full((size, how_many), -1, np.int32)
            for j, (uid, served) in enumerate(chunk):
                for r, got in enumerate(served[:how_many]):
                    row = self.model.Y.row_of(str(got.get("id")))
                    served_rows[j, r] = -1 if row is None else row
            at = jnp.asarray(np.maximum(served_rows, 0))
            ref_scores, ib, im = jax.device_get((
                self.exact._scores_of(Y, X, at),
                jnp.take(bits, at), jnp.take(marginal, at)))
            qb, qm = np.asarray(qbits), np.asarray(qmarginal)
            for j, (uid, served) in enumerate(chunk):
                self.checked += 1
                free = int(qm[j]) | im[j]
                if np.any(free[served_rows[j] >= 0]):
                    self.met_marginal_bit += 1
                # candidates under SOME reading: the bits that are
                # marginal on either side are free to agree
                nearest = np.bitwise_count((ib[j] ^ int(qb[j])) & ~free)
                problems += self._compare(
                    uid, served, how_many, served_rows[j], ref_scores[j],
                    nearest, sure_s[j], sure_i[j], row_ids, exclude_known)
                got_rows = set(served_rows[j].tolist())
                self.recall_of += how_many
                self.recall_hits += sum(
                    1 for s, i in zip(exact_s[j], exact_i[j])
                    if np.isfinite(s) and int(i) in got_rows)
        return problems

    def _compare(self, uid, served, how_many, served_rows, ref_scores,
                 nearest, sure_s, sure_i, row_ids, exclude_known):
        what = f"/recommend/{uid}"
        n_sure = int(np.isfinite(sure_s).sum())
        if len(served) > how_many or len(served) < min(how_many, n_sure):
            return [f"{what}: {len(served)} results, wanted {how_many} "
                    f"({n_sure} certain candidates)"]
        known = self.model.get_known_items(uid) if exclude_known else ()
        ids = [str(g.get("id")) for g in served]
        out = []
        if len(set(ids)) != len(ids):
            out.append(f"{what}: an item is returned twice")
        last = np.inf
        for r, got in enumerate(served):
            if ids[r] in known:
                out.append(f"{what} rank {r}: known item {ids[r]} returned")
            if served_rows[r] < 0:
                out.append(f"{what} rank {r}: unknown item {ids[r]}")
                continue
            if nearest[r] > self.radius:
                out.append(f"{what} rank {r}: item {ids[r]} lies "
                           f"{int(nearest[r])} bits from the query's "
                           f"bucket under every reading, radius "
                           f"{self.radius}")
            want = float(ref_scores[r])
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
            if want > last + tol:
                out.append(f"{what} rank {r}: {ids[r]} ({want!r}) after "
                           f"a lower score ({last!r})")
            last = want
        # no certain candidate above the last one returned is missing
        # (all of them, where fewer came back than were asked for)
        floor = last if len(served) == how_many else -np.inf
        got_rows = set(int(r) for r in served_rows)
        for s, i in zip(sure_s.tolist(), sure_i.tolist()):
            if not np.isfinite(s) or int(i) in got_rows:
                continue
            if s > floor + max(SCORE_ATOL, SCORE_RTOL * abs(s)):
                out.append(f"{what}: {row_ids[int(i)]} ({s!r}) is a "
                           f"candidate under every reading, scores above "
                           f"the last item returned ({floor!r}) and is "
                           "missing")
        return out

    def recall(self) -> float | None:
        """Of the exact scan's ``howMany`` best (``als_reference``: no
        pruning), the share the checked pruned answers held."""
        return self.recall_hits / self.recall_of if self.recall_of else None
