"""Metric/span name lint (ISSUE 5 satellite): every counter, gauge,
and span name literal in the source must appear in the
docs/OBSERVABILITY.md catalog tables and follow the naming rules —
the same keep-the-namespace-from-rotting contract RESILIENCE.md
already enforces for fault-point names.

The walk is AST-based (not regex) so multi-line call sites and
keyword-argument forms are seen.  Names are collected from the
call-site surface of MetricsRegistry and Tracer:

- ``.inc("<counter>")``
- ``.set_gauge("<gauge>", ...)`` / ``.gauge_fn("<gauge>", ...)``
- ``.span("<span>")`` / ``.child_span(parent, "<span>")`` /
  ``.record_span("<span>", ...)``

Request spans are built dynamically as ``f"{service}.request"``
(lambda_rt/http.py), so the known service tiers' request spans are
asserted against the catalog explicitly.
"""

from __future__ import annotations

import ast
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC = REPO / "oryx_tpu"
DOC = REPO / "docs" / "OBSERVABILITY.md"

# snake_case on both sides of the single dot for spans; plain
# snake_case for counters/gauges
_SPAN_RE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")

# (method attribute, index of the positional name argument)
# mark/step/annotation: obs/trace.py's drain phases, the steps inside
# them and bare profiler annotations; _await_locked: the batcher's
# annotated condition wait
_SPAN_METHODS = {"span": 0, "child_span": 1, "record_span": 0,
                 "mark": 0, "step": 0, "annotation": 0,
                 "_await_locked": 0, "phase": 0}
_COUNTER_METHODS = {"inc": 0}
_GAUGE_METHODS = {"set_gauge": 0, "gauge_fn": 0}

# dynamic f"{service}.request" spans (lambda_rt/http.py): one per
# tier with an HTTP surface — router, serving, and the headless
# tiers' side-door ObsServer — not literals the AST walk can see
_DYNAMIC_REQUEST_SPANS = {"router.request", "serving.request",
                          "speed.request", "batch.request",
                          "mirror.request"}


def _literal_arg(call: ast.Call, index: int) -> str | None:
    if len(call.args) > index:
        arg = call.args[index]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value
    return None


def _collect_names():
    """{kind: {name: [file:line, ...]}} for every literal call site."""
    found: dict[str, dict[str, list[str]]] = {
        "span": {}, "counter": {}, "gauge": {}}
    for path in sorted(SRC.rglob("*.py")):
        tree = ast.parse(path.read_text(encoding="utf-8"),
                         filename=str(path))
        rel = path.relative_to(REPO)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            attr = node.func.attr
            for kind, methods in (("span", _SPAN_METHODS),
                                  ("counter", _COUNTER_METHODS),
                                  ("gauge", _GAUGE_METHODS)):
                if attr in methods:
                    name = _literal_arg(node, methods[attr])
                    if name is not None:
                        found[kind].setdefault(name, []).append(
                            f"{rel}:{node.lineno}")
    return found


def _catalog_names() -> set[str]:
    """Backticked names from the first cell of every catalog table row
    in docs/OBSERVABILITY.md (prose mentions elsewhere don't count as
    cataloguing)."""
    names = set()
    for line in DOC.read_text(encoding="utf-8").splitlines():
        if not line.startswith("|"):
            continue
        first_cell = line.split("|")[1].strip()
        m = re.fullmatch(r"`([^`]+)`", first_cell)
        if m:
            names.add(m.group(1))
    return names


@pytest.fixture(scope="module")
def source_names():
    return _collect_names()


@pytest.fixture(scope="module")
def catalog():
    assert DOC.is_file(), "docs/OBSERVABILITY.md is the catalog source"
    names = _catalog_names()
    assert names, "no catalog tables parsed from OBSERVABILITY.md"
    return names


def test_walk_sees_the_known_call_sites(source_names):
    # the lint is only as good as its walk: pin a known literal of
    # each kind so an AST/API drift fails loudly instead of silently
    # linting nothing
    assert "router.merge" in source_names["span"]
    assert "serving.queue_wait" in source_names["span"]
    assert "serving.scan" in source_names["span"]
    assert "serving.await_work" in source_names["span"]
    assert "partial_answers" in source_names["counter"]
    assert "ingest_to_servable_ms" in source_names["gauge"]
    assert "update_lag_records" in source_names["gauge"]


@pytest.mark.parametrize("name", [
    "serving.upload", "serving.launch", "serving.device_wait",
    "serving.fetch", "serving.release"])
def test_the_drain_steps_are_walked_and_catalogued(name, source_names,
                                                   catalog):
    """PR 39: the steps inside a drain's phases (``DrainPhases.step``)
    and the dispatcher's annotation between a drain and its next wait
    are literals the walk sees, and rows of the span table."""
    assert name in source_names["span"]
    assert name in catalog


@pytest.mark.parametrize("surface", ["queue_wait", "scoring_batcher"])
def test_the_batchers_verdict_is_catalogued_key_by_key(surface):
    """PR 40: what the batcher says of its verdict, on every drain's
    ``serving.queue_wait`` span and in ``/metrics``' ``scoring_batcher``
    block, is catalogued attribute by attribute: each key the code
    emits is backticked in the span's row, or in the first cell of a
    row of the block's table."""
    import time

    from oryx_tpu.serving.batcher import TopNBatcher

    batcher = TopNBatcher(pipeline=1)
    try:
        with batcher._cond:
            note = batcher._bind_locked(time.monotonic())
        stats = batcher.stats()
    finally:
        batcher.close()
    assert {"cycle_behind_ms", "cycle_shared_ms", "cycle_n",
            "overlap_share"} <= set(note) & set(stats)
    rows = [line for line in DOC.read_text(encoding="utf-8").splitlines()
            if line.startswith("|")]
    if surface == "queue_wait":
        keys = note
        # (the span table's row: the anatomy table names the span too)
        row = next(r for r in rows
                   if r.split("|")[1].strip() == "`serving.queue_wait`")
        documented = set(re.findall(r"`([a-z_]+)`", row))
    else:
        keys = stats
        documented = {name for r in rows
                      for name in re.findall(r"`([a-z_]+)`",
                                             r.split("|")[1])}
    assert not sorted(set(keys) - documented), sorted(
        set(keys) - documented)


def test_every_source_name_is_catalogued(source_names, catalog):
    missing = [
        f"{kind} {name!r} ({', '.join(sites)})"
        for kind, names in source_names.items()
        for name, sites in sorted(names.items())
        if name not in catalog]
    assert not missing, (
        "names used in source but absent from the docs/OBSERVABILITY.md"
        " catalog tables:\n  " + "\n  ".join(missing))


def test_dynamic_request_spans_are_catalogued(catalog):
    missing = _DYNAMIC_REQUEST_SPANS - catalog
    assert not missing, (
        f"dynamic request spans missing from the catalog: {missing}")


def _module_tuple(path: pathlib.Path, name: str) -> tuple[str, ...]:
    """A module-level ``NAME = ("...", ...)`` string-tuple literal,
    extracted via AST (no import needed)."""
    tree = ast.parse(path.read_text(encoding="utf-8"),
                     filename=str(path))
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets):
            value = node.value
            assert isinstance(value, ast.Tuple), f"{name} not a tuple"
            out = []
            for el in value.elts:
                assert isinstance(el, ast.Constant) \
                    and isinstance(el.value, str), f"{name}: non-string"
                out.append(el.value)
            return tuple(out)
    raise AssertionError(f"{name} not found in {path}")


def test_anatomy_stage_names_are_catalogued(catalog):
    """The /admin/tail stage taxonomy (obs/anatomy.py STAGES) must be
    in the OBSERVABILITY.md stage table — same rot-prevention contract
    as the span names.  Stages are tier.operation like spans, except
    the designated residue bucket ``untraced``."""
    stages = _module_tuple(SRC / "obs" / "anatomy.py", "STAGES")
    assert len(stages) >= 5
    missing = set(stages) - catalog
    assert not missing, \
        f"anatomy stages missing from the catalog: {sorted(missing)}"
    for name in stages:
        assert name == "untraced" or _SPAN_RE.fullmatch(name), \
            f"stage {name!r} must be tier.operation snake_case"


def test_wide_event_fields_are_catalogued(catalog):
    """Every wide-event field (obs/events.py FIELDS) must be in the
    OBSERVABILITY.md schema table, snake_case."""
    fields = _module_tuple(SRC / "obs" / "events.py", "FIELDS")
    assert len(fields) >= 6
    missing = set(fields) - catalog
    assert not missing, \
        f"wide-event fields missing from the catalog: {sorted(missing)}"
    for name in fields:
        assert _NAME_RE.fullmatch(name), \
            f"wide-event field {name!r} must be snake_case"


def test_names_follow_the_naming_rules(source_names):
    bad = []
    for name, sites in sorted(source_names["span"].items()):
        if not _SPAN_RE.fullmatch(name):
            bad.append(f"span {name!r} must be tier.operation "
                       f"snake_case ({', '.join(sites)})")
    for kind in ("counter", "gauge"):
        for name, sites in sorted(source_names[kind].items()):
            if not _NAME_RE.fullmatch(name):
                bad.append(f"{kind} {name!r} must be snake_case "
                           f"({', '.join(sites)})")
    assert not bad, "\n".join(bad)
