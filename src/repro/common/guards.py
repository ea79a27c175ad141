"""Runtime guards for the repo's recurring bug classes (staticcheck's twin).

The static pass (``repro.analysis.staticcheck``) catches what an AST can
prove; these context managers catch the rest at runtime:

* :class:`CompileGuard` — asserts a bounded number of NEW jit compilations
  across a region.  Generalizes the hand-rolled ``Endpoint.compile_count()``
  before/after counters that every churn test and benchmark reinvented
  (PR 3's 94-silent-retraces class).
* :func:`no_host_sync` — disallows implicit device->host transfers inside a
  region via ``jax.transfer_guard_device_to_host``.  Enforced on GPU/TPU;
  on the CPU backend transfers are zero-copy and the guard is advisory,
  which is why the static SC01 rule exists at all.
* :func:`strict_numerics` — strict dtype promotion (mixed-precision
  accumulation must be spelled out, not inherited from promotion rules)
  with opt-in ``debug_nans``.

All three are exposed to tests as pytest markers via ``tests/conftest.py``.
"""
from __future__ import annotations

import contextlib
import threading

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_lock = threading.Lock()
_compile_events = 0
_listener_installed = False


def jit_cache_size(fn) -> int:
    """Compilation count of one jitted callable (``_cache_size`` of the
    installed JAX's jit wrapper)."""
    return int(fn._cache_size())


def _install_listener() -> None:
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        from jax._src import monitoring

        def _on_event(name: str, *args, **kwargs) -> None:
            global _compile_events
            if name == _COMPILE_EVENT:
                _compile_events += 1

        monitoring.register_event_duration_secs_listener(_on_event)
        _listener_installed = True


def global_compile_count() -> int:
    """Process-wide backend-compile count (monotonic, delta-only semantics:
    compiles before the first call are not included)."""
    _install_listener()
    return _compile_events


class CompileGuard:
    """Assert that a region performs at most ``max_retraces`` compilations.

    Watch targets are objects exposing ``compile_count()`` (e.g. the paged
    ``Endpoint``) or jitted callables (counted via their cache size).  With
    no targets, the guard watches the process-wide compile counter — the
    right tool when the jits live behind an API (``route_window``'s fused
    programs, the solver's blocked bodies).

    >>> with CompileGuard(endpoint) as g:
    ...     run_churn()
    >>> g.retraces()
    0

    ``max_retraces=None`` only measures; any int raises ``AssertionError``
    on exit when exceeded.
    """

    def __init__(self, *watch, max_retraces: int | None = 0, label: str = ""):
        self.watch = watch
        self.max_retraces = max_retraces
        self.label = label
        self._before: list[int] | None = None

    @staticmethod
    def _count(obj) -> int:
        counter = getattr(obj, "compile_count", None)
        if callable(counter):
            return int(counter())
        return jit_cache_size(obj)

    def _counts(self) -> list[int]:
        if self.watch:
            return [self._count(o) for o in self.watch]
        return [global_compile_count()]

    def __enter__(self) -> "CompileGuard":
        if not self.watch:
            _install_listener()
        self._before = self._counts()
        return self

    def retraces(self) -> int:
        assert self._before is not None, "CompileGuard not entered"
        return sum(self._counts()) - sum(self._before)

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None or self.max_retraces is None:
            return
        seen = self.retraces()
        if seen > self.max_retraces:
            what = self.label or "guarded region"
            raise AssertionError(
                f"CompileGuard: {what} compiled {seen} time(s), expected at "
                f"most {self.max_retraces} — a shape/dtype/static-arg is "
                "churning the jit cache (see staticcheck rule SC02)."
            )


@contextlib.contextmanager
def no_host_sync():
    """Disallow implicit device->host transfers inside the region.

    Explicit fetches (``jax.device_get``) stay allowed: the point is to
    catch accidental per-element syncs (``float(dev)``, ``if dev:``), not
    to forbid reading results.  On CPU the XLA transfer guard never fires
    (host==device, transfers are zero-copy), so this is load-bearing on
    accelerators and documentation on CPU — staticcheck SC01 covers the
    gap statically.
    """
    with jax.transfer_guard_device_to_host("disallow"):
        yield


@contextlib.contextmanager
def strict_numerics(debug_nans: bool = False):
    """Strict dtype promotion (+ optional NaN checking) for a region.

    Under ``numpy_dtype_promotion('strict')`` mixed strong dtypes raise
    instead of silently promoting — the solver's fp32-accumulation
    discipline stays explicit.  Python scalars remain weak-typed and fine.
    """
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.numpy_dtype_promotion("strict"))
        if debug_nans:
            stack.enter_context(jax.debug_nans(True))
        yield
