"""Tier-1 unit tests for oryx_tpu.common (reference analogs:
ConfigUtilsTest, TextUtilsTest, RandomManagerTest, ExecUtilsTest,
AutoReadWriteLockTest, DoubleWeightedMeanTest, IOUtilsTest)."""

import threading
import time

import pytest

from oryx_tpu.common import hocon, io_utils, lang, text
from oryx_tpu.common.config import get_default, overlay_on
from oryx_tpu.common.rand import RandomManager
from oryx_tpu.common.stats import DoubleWeightedMean


# -- hocon / config ---------------------------------------------------------

def test_hocon_basic():
    d = hocon.loads("""
    a = 1
    b { c = "x", d = [1, 2, 3] }
    b.e = true
    f = null
    # comment
    g = 1.5 // other comment
    """)
    assert d == {"a": 1, "b": {"c": "x", "d": [1, 2, 3], "e": True},
                 "f": None, "g": 1.5}


def test_hocon_substitution():
    d = hocon.loads("base = { x = 1 }\nother = { config = ${base} }")
    assert d["other"]["config"] == {"x": 1}


def test_hocon_merge_nested():
    base = hocon.loads("a { b = 1\n c = 2 }")
    over = hocon.loads("a { c = 3 }")
    assert hocon.merge(base, over) == {"a": {"b": 1, "c": 3}}


def test_default_config_key_surface():
    cfg = get_default()
    # spot-check the full reference key surface
    assert cfg.get_string("oryx.input-topic.message.topic") == "OryxInput"
    assert cfg.get_string("oryx.update-topic.message.topic") == "OryxUpdate"
    assert cfg.get_int("oryx.update-topic.message.max-size") == 16777216
    assert cfg.get_int("oryx.batch.streaming.generation-interval-sec") == 21600
    assert cfg.get_int("oryx.speed.streaming.generation-interval-sec") == 10
    assert cfg.get_double("oryx.serving.min-model-load-fraction") == 0.8
    assert cfg.get_double("oryx.ml.eval.test-fraction") == 0.1
    assert cfg.get_optional_string("oryx.batch.update-class") is None
    assert cfg.get_int("oryx.als.hyperparams.features") == 10
    assert cfg.get_bool("oryx.als.implicit") is True
    assert cfg.get_string("oryx.kmeans.initialization-strategy") == "k-means||"
    assert cfg.get_string("oryx.rdf.hyperparams.impurity") == "entropy"
    # substitution carried streaming config through
    assert cfg.get("oryx.batch.streaming.config.jax.matrix-dtype") == "float32"


def test_overlay_and_serialize():
    cfg = overlay_on({"oryx.als.hyperparams.features": 42}, get_default())
    assert cfg.get_int("oryx.als.hyperparams.features") == 42
    rt = type(cfg).deserialize(cfg.serialize())
    assert rt.get_int("oryx.als.hyperparams.features") == 42


def test_pretty_print_redacts_password():
    cfg = overlay_on({"oryx.serving.api.password": "hunter2"}, get_default())
    assert "hunter2" not in cfg.pretty_print()
    assert "*****" in cfg.pretty_print()


def test_user_conf_substitutes_base_keys(tmp_path):
    # Typesafe Config resolves substitutions after merge: user files may
    # reference keys defined only in the packaged defaults
    p = tmp_path / "user.conf"
    p.write_text("oryx.speed.streaming.config = ${oryx.default-streaming-config}\n")
    from oryx_tpu.common.config import from_file
    cfg = from_file(str(p))
    assert cfg.get_bool("oryx.speed.streaming.config.jax.donate-buffers") is True


def test_config_mutation_isolated_from_defaults():
    from oryx_tpu.common.config import from_dict
    d2 = from_dict({"oryx.als.iterations": 99})
    d2.as_dict()["oryx"]["als"]["hyperparams"]["features"] = 777
    assert get_default().get("oryx.als.hyperparams.features") == 10


def test_properties_render_hocon_booleans():
    assert get_default().to_properties()["oryx.als.implicit"] == "true"


def test_typed_getters_raise():
    cfg = get_default()
    with pytest.raises(KeyError):
        cfg.get("oryx.nope")
    with pytest.raises(TypeError):
        cfg.get_int("oryx.input-topic.message.topic")


# -- text -------------------------------------------------------------------

def test_csv_roundtrip():
    row = ["a", "b with, comma", 'quote"inside', "1.5"]
    line = text.join_delimited(row)
    assert text.parse_delimited(line) == row


def test_parse_delimited_simple():
    assert text.parse_delimited("a,b,c") == ["a", "b", "c"]
    assert text.parse_delimited("a,,c") == ["a", "", "c"]


def test_join_json_and_parse():
    line = text.join_json(["X", "user1", [0.5, -1.25], ["item1"]])
    assert line == '["X","user1",[0.5,-1.25],["item1"]]'
    parsed = text.parse_json_array(line)
    assert parsed[1] == "user1"
    assert parsed[2] == [0.5, -1.25]


def test_parse_input_line_json_or_csv():
    assert text.parse_input_line('["u","i","5",""]') == ["u", "i", "5", ""]
    assert text.parse_input_line("u,i,5,123") == ["u", "i", "5", "123"]


def test_pmml_delimited():
    assert text.parse_pmml_delimited('a "b c"  d') == ["a", "b c", "d"]
    assert text.join_pmml_delimited_numbers([1, -2.5]) == "1 -2.5"


def test_pmml_delimited_round_trips_special_tokens():
    for row in (["a", ""], ['"'], ["a b", 'c"d'], ["x"]):
        assert text.parse_pmml_delimited(text.join_pmml_delimited(row)) == row


def test_parse_input_line_null_is_empty():
    assert text.parse_input_line('["u","i",null,"123"]') == ["u", "i", "", "123"]


# -- random -----------------------------------------------------------------

def test_random_deterministic_under_test_seed():
    RandomManager.use_test_seed()
    a = RandomManager.random().random(5)
    b = RandomManager.random().random(5)
    assert (a == b).all()


# -- lang -------------------------------------------------------------------

def test_collect_in_parallel_order():
    out = lang.collect_in_parallel(10, lambda i: i * i, parallelism=4)
    assert out == [i * i for i in range(10)]


def test_load_class_and_instance():
    cls = lang.load_class("oryx_tpu.common.stats.DoubleWeightedMean")
    assert cls is DoubleWeightedMean
    inst = lang.load_instance("oryx_tpu.common.stats.DoubleWeightedMean")
    assert isinstance(inst, DoubleWeightedMean)


def test_auto_rw_lock():
    lock = lang.AutoReadWriteLock()
    state = []

    with lock.read():
        state.append("r")
    with lock.write():
        state.append("w")

    # a writer blocks until readers release
    entered = threading.Event()

    def writer():
        with lock.write():
            entered.set()

    with lock.read():
        t = threading.Thread(target=writer)
        t.start()
        assert not entered.wait(0.05)
    assert entered.wait(1.0)
    t.join()


def test_reentrant_read_with_waiting_writer():
    # nested read acquisition must not deadlock while a writer waits
    lock = lang.AutoReadWriteLock()
    done = threading.Event()

    def nested_reader():
        with lock.read():
            time.sleep(0.05)  # let the writer start waiting
            with lock.read():
                done.set()

    t1 = threading.Thread(target=nested_reader)
    t1.start()
    time.sleep(0.01)

    def writer():
        with lock.write():
            pass

    t2 = threading.Thread(target=writer)
    t2.start()
    assert done.wait(2.0), "nested read deadlocked behind waiting writer"
    t1.join(2.0)
    t2.join(2.0)


def test_load_instance_propagates_ctor_errors():
    with pytest.raises(ZeroDivisionError):
        lang.load_instance("tests.test_common._ExplodingPlugin", object())


class _ExplodingPlugin:
    def __init__(self, config=None):
        1 / 0


def test_collect_in_parallel_zero_parallelism():
    assert lang.collect_in_parallel(5, lambda i: i, parallelism=0) == list(range(5))


def test_rate_limit_check():
    check = lang.RateLimitCheck(1000.0)
    assert check.test() is True
    assert check.test() is False


# -- stats ------------------------------------------------------------------

def test_weighted_mean():
    m = DoubleWeightedMean()
    m.increment(1.0, 1.0)
    m.increment(3.0, 3.0)
    assert abs(m.result - 2.5) < 1e-12
    assert m.count == 2


# -- io ---------------------------------------------------------------------

def test_strip_scheme():
    assert io_utils.strip_scheme("file:/tmp/x") == "/tmp/x"
    assert io_utils.strip_scheme("file:///tmp/x") == "/tmp/x"
    assert io_utils.strip_scheme("/tmp/x") == "/tmp/x"


def test_choose_free_port():
    p = io_utils.choose_free_port()
    assert 0 < p < 65536


def _fresh_compile_cache(monkeypatch):
    """Un-latch the process-wide cache state; returns jax's current
    cache dir for the caller to restore."""
    import jax
    from jax._src import compilation_cache as _cc

    from oryx_tpu.common import compile_cache

    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    # JAX memoizes the cache instance at first use; earlier tests that
    # started layers may have initialized it at another path
    _cc.reset_cache()
    return jax.config.jax_compilation_cache_dir


def test_compile_cache_enable_from_config(tmp_path, monkeypatch):
    import jax
    from jax._src import compilation_cache as _cc

    from oryx_tpu.common import compile_cache
    from oryx_tpu.common.config import from_dict

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = _fresh_compile_cache(monkeypatch)
    try:
        cc = str(tmp_path / "cc")
        cfg = from_dict({"oryx.compile-cache-dir": cc,
                         "oryx.compile-cache-min-compile-secs": 0.0})
        assert compile_cache.enable_from_config(cfg) == cc
        assert jax.config.jax_compilation_cache_dir == cc
        # first configuration wins process-wide
        cfg2 = from_dict({"oryx.compile-cache-dir": "/elsewhere"})
        assert compile_cache.enable_from_config(cfg2) == cc
        # a compiled executable lands on disk
        f = jax.jit(lambda x: x * 2 + 1)
        assert float(f(jax.numpy.float32(3))) == 7.0
        import pathlib
        assert list(pathlib.Path(cc).iterdir())
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        _cc.reset_cache()


def test_compile_cache_env_placement_outranks_config(tmp_path, monkeypatch):
    """An exported JAX_COMPILATION_CACHE_DIR is the operator's (or the
    chip harness's) placement: the program sets NO directory in code."""
    import jax
    from jax._src import compilation_cache as _cc

    from oryx_tpu.common import compile_cache
    from oryx_tpu.common.config import from_dict

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    prev = _fresh_compile_cache(monkeypatch)
    try:
        cfg = from_dict({"oryx.compile-cache-dir": str(tmp_path / "cfg")})
        assert compile_cache.enable_from_config(cfg) == placed
        # untouched: whatever jax itself read from the environment at
        # import stays; the config key's path was never written to it
        assert jax.config.jax_compilation_cache_dir == prev
        assert not (tmp_path / "cfg").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        _cc.reset_cache()


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    """Unset, the default resolves to ONE fixed git-ignored directory
    inside the checkout — from the package's own location, never the
    working directory, a temp name, a pid or a timestamp."""
    import pathlib

    import jax
    from jax._src import compilation_cache as _cc

    from oryx_tpu.common import compile_cache
    from oryx_tpu.common.config import get_default

    repo = pathlib.Path(__file__).resolve().parents[1]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir("/")
    prev = _fresh_compile_cache(monkeypatch)
    try:
        want = str(repo / ".jax_cache")
        assert compile_cache.enable_from_config(get_default()) == want
        assert jax.config.jax_compilation_cache_dir == want
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        _cc.reset_cache()


def test_compile_cache_disabled_when_null(monkeypatch):
    from oryx_tpu.common import compile_cache
    from oryx_tpu.common.config import from_dict

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    cfg = from_dict({"oryx.compile-cache-dir": None})
    assert compile_cache.enable_from_config(cfg) is None


def test_compile_cache_key_of_a_pallas_program_ignores_the_call_path(
        tmp_path, monkeypatch):
    """jax hashes a pallas_call's Mosaic payload into the persistent
    cache key as opaque bytes, call-stack locations included, so the AOT
    warmup and the live dispatch — two call paths to one kernel — would
    never share an entry.  enable_from_config drops the call stack from
    locations; the TPU lowering is then identical from any caller."""
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache as _cc
    from jax.experimental import pallas as pl

    from oryx_tpu.common import compile_cache
    from oryx_tpu.common.config import from_dict

    def lowered_from_two_call_paths():
        # a FRESH jit each time: a cached trace would hide the flag
        @jax.jit
        def double(x):
            def kern(x_ref, o_ref):
                o_ref[...] = x_ref[...] * 2.0
            return pl.pallas_call(
                kern, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

        x = jax.ShapeDtypeStruct((8, 128), jnp.float32)

        def path_a():
            return double.trace(x).lower(
                lowering_platforms=("tpu",)).as_text()

        def path_b():
            return (lambda: double.trace(x).lower(
                lowering_platforms=("tpu",)).as_text())()

        a = path_a()
        double.clear_cache()
        return a, path_b()

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev_dir = _fresh_compile_cache(monkeypatch)
    prev_tb = jax.config.jax_include_full_tracebacks_in_locations
    try:
        jax.config.update("jax_include_full_tracebacks_in_locations", True)
        a, b = lowered_from_two_call_paths()
        assert a != b  # the hazard, on this jax
        compile_cache.enable_from_config(from_dict(
            {"oryx.compile-cache-dir": str(tmp_path / "cc")}))
        a, b = lowered_from_two_call_paths()
        assert a == b
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          prev_tb)
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        _cc.reset_cache()
