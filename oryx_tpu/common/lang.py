"""Concurrency and reflection utilities.

Reference: framework/oryx-common/src/main/java/com/cloudera/oryx/common/lang/
 - ClassUtils.java:89   load class/instance by name (the plugin mechanism)
 - ExecUtils.java:93    doInParallel / collectInParallel fan-out
 - AutoReadWriteLock.java:37, AutoLock.java   ARM-style lock wrappers
 - RateLimitCheck.java:28                     rate-limited logging gate
 - LoggingCallable.java:31                    log-and-swallow wrapper
 - OryxShutdownHook.java:32, JVMUtils.java:26 ordered shutdown hooks
"""

from __future__ import annotations

import atexit
import contextlib
import importlib
import inspect
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, Iterator, Sequence, TypeVar

_log = logging.getLogger(__name__)

T = TypeVar("T")

__all__ = [
    "load_class", "load_instance", "do_in_parallel", "collect_in_parallel",
    "AutoReadWriteLock", "BackgroundShare", "RateLimitCheck", "logging_call",
    "ShutdownHook",
]


# -- plugin loading ---------------------------------------------------------

def load_class(name: str) -> type:
    """Load a class by ``pkg.module.Class`` import path
    (reference: ClassUtils.loadClass, the update-class / model-manager-class
    plugin mechanism)."""
    module_name, _, cls_name = name.rpartition(".")
    if not module_name:
        raise ValueError(f"not a qualified class name: {name!r}")
    module = importlib.import_module(module_name)
    try:
        return getattr(module, cls_name)
    except AttributeError as e:
        raise ImportError(f"no class {cls_name!r} in module {module_name!r}") from e


def load_instance(name: str, *args: Any) -> Any:
    """Instantiate by name, preferring a ctor accepting the given args and
    falling back to no-arg (reference: ClassUtils.loadInstanceOf with
    optional (Config) constructor).

    Constructor choice is made by signature inspection, not by catching
    TypeError, so real errors raised inside the constructor propagate.
    """
    cls = load_class(name)
    if args:
        try:
            inspect.signature(cls).bind(*args)
            accepts = True
        except TypeError:
            accepts = False
        if accepts:
            return cls(*args)
    return cls()


# -- parallel execution -----------------------------------------------------

def do_in_parallel(num_items: int, fn: Callable[[int], Any],
                   parallelism: int | None = None) -> None:
    """Run fn(0..num_items-1), up to ``parallelism`` at a time
    (reference: ExecUtils.doInParallel)."""
    collect_in_parallel(num_items, fn, parallelism)


def collect_in_parallel(num_items: int, fn: Callable[[int], T],
                        parallelism: int | None = None) -> list[T]:
    """Run fn over indices and collect results in index order
    (reference: ExecUtils.collectInParallel :93)."""
    if num_items <= 0:
        return []
    parallelism = num_items if parallelism is None else max(1, parallelism)
    if parallelism == 1 or num_items == 1:
        return [fn(i) for i in range(num_items)]
    with ThreadPoolExecutor(max_workers=min(parallelism, num_items)) as pool:
        return list(pool.map(fn, range(num_items)))


# -- locks ------------------------------------------------------------------

class _RWLock:
    """Writer-preferring reader/writer lock, reentrant like
    java.util.concurrent.ReentrantReadWriteLock: a thread already holding
    the read (or write) lock may re-acquire it even while a writer waits,
    and the writer thread may take read locks."""

    def __init__(self):
        self._cond = threading.Condition()
        self._read_holds = threading.local()
        self._readers = 0
        self._writer_thread: int | None = None
        self._writer_depth = 0
        self._writers_waiting = 0

    def _holds(self) -> int:
        return getattr(self._read_holds, "count", 0)

    def acquire_read(self):
        me = threading.get_ident()
        with self._cond:
            if self._holds() == 0 and self._writer_thread != me:
                while self._writer_depth or self._writers_waiting:
                    self._cond.wait()
            self._readers += 1
            self._read_holds.count = self._holds() + 1

    def release_read(self):
        with self._cond:
            self._readers -= 1
            self._read_holds.count = self._holds() - 1
            if self._readers == 0:
                self._cond.notify_all()

    def acquire_write(self):
        me = threading.get_ident()
        with self._cond:
            if self._writer_thread == me:
                self._writer_depth += 1
                return
            self._writers_waiting += 1
            # readers held by this same thread would deadlock here; that
            # (read->write upgrade) deadlocks in the reference's lock too
            while self._writer_depth or self._readers:
                self._cond.wait()
            self._writers_waiting -= 1
            self._writer_thread = me
            self._writer_depth = 1

    def release_write(self):
        with self._cond:
            self._writer_depth -= 1
            if self._writer_depth == 0:
                self._writer_thread = None
                self._cond.notify_all()


class AutoReadWriteLock:
    """Context-manager reader/writer lock
    (reference: AutoReadWriteLock.java:37 — autoReadLock()/autoWriteLock())."""

    def __init__(self):
        self._lock = _RWLock()

    @contextlib.contextmanager
    def read(self) -> Iterator[None]:
        self._lock.acquire_read()
        try:
            yield
        finally:
            self._lock.release_read()

    @contextlib.contextmanager
    def write(self) -> Iterator[None]:
        self._lock.acquire_write()
        try:
            yield
        finally:
            self._lock.release_write()


# -- background work under the interpreter lock --------------------------------

class BackgroundShare:
    """Keeps a thread that computes in the background of request threads
    to a share of the interpreter lock.

    CPython hands the lock over when its holder blocks or after the
    switch interval, to whichever waiter the OS wakes.  Two background
    threads that pass records to each other (a micro-batch publishing
    ``UP`` records, the update consumer applying them) hand it to EACH
    OTHER: a request thread that has just been given its result by the
    device waited through 5-10 ms of that in 3% of requests (PERF.md,
    PR 27), whatever the switch interval.  A background thread that
    rests ``1 / share - 1`` times as long as it worked leaves the lock
    free most of the time, and a request thread that asks finds it so.

    ``with share.work():`` around each piece (a record, a chunk of a
    loop); rests are taken once ``burst_s`` of work has added up, outside
    any lock the piece held.  One instance a thread.  The price is the
    background work's own pace: ``share`` 0.25 makes a burst of it last
    four times as long."""

    def __init__(self, share: float = 0.25, burst_s: float = 0.0005):
        self._rest = 1.0 / share - 1.0
        self._min_rest = burst_s * self._rest
        self._owed = 0.0

    @contextlib.contextmanager
    def work(self) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._owed += (time.perf_counter() - t0) * self._rest
            if self._owed >= self._min_rest:
                time.sleep(self._owed)  # wall-clock: yields the interpreter
                self._owed = 0.0


# -- rate limiting ----------------------------------------------------------

class RateLimitCheck:
    """True at most once per interval (reference: RateLimitCheck.java:28)."""

    def __init__(self, interval_sec: float):
        self._interval = interval_sec
        self._next = time.monotonic()
        self._lock = threading.Lock()

    def test(self) -> bool:
        with self._lock:
            now = time.monotonic()
            if now >= self._next:
                self._next = now + self._interval
                return True
            return False


# -- logging wrapper --------------------------------------------------------

def logging_call(fn: Callable[[], T], name: str = "task") -> Callable[[], T | None]:
    """Wrap a callable to log (not raise) exceptions — for fire-and-forget
    threads (reference: LoggingCallable.java:31)."""

    def _wrapped() -> T | None:
        try:
            return fn()
        except Exception:  # noqa: BLE001 — deliberately broad; background task
            _log.exception("Unexpected error in %s", name)
            return None

    return _wrapped


# -- shutdown hooks ---------------------------------------------------------

class ShutdownHook:
    """Ordered close-on-exit registry (reference: OryxShutdownHook.java:32,
    JVMUtils.closeAtShutdown). Closeables run in reverse registration order."""

    def __init__(self):
        self._closeables: list[Any] = []
        self._lock = threading.Lock()
        self._triggered = False
        atexit.register(self.run)

    def add_close_at_shutdown(self, closeable: Any) -> None:
        with self._lock:
            if self._triggered:
                raise RuntimeError("shutdown already in progress")
            self._closeables.append(closeable)

    def run(self) -> None:
        with self._lock:
            if self._triggered:
                return
            self._triggered = True
            closeables = list(reversed(self._closeables))
        for c in closeables:
            with contextlib.suppress(Exception):
                c.close()


GLOBAL_SHUTDOWN_HOOK = ShutdownHook()


def close_at_shutdown(closeable: Any) -> None:
    GLOBAL_SHUTDOWN_HOOK.add_close_at_shutdown(closeable)
