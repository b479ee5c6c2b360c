"""Compile-latency plane (ISSUE 7 tentpole, part 1): the persistent XLA
compilation cache as a first-class, observable subsystem.

Every ``serve`` boot and every training start re-JITs its programs from
scratch unless something remembers them.  JAX ships a persistent
compilation cache (serialized XLA executables keyed by a hash of the
HLO + compile options + backend fingerprint); this module turns it on by
default, puts it where an operator can place it, and makes it
assertable:

- :func:`configure` resolves ONE directory.  ``$JAX_COMPILATION_CACHE_DIR``
  — jax's own variable, the one a driver or a cluster sets from outside —
  is the directory whenever it is set, and this module then never writes
  any other value into ``jax_compilation_cache_dir``.  Unset, the
  directory is ``<checkout>/.data/cache/jax``, computed from the
  package's own location: a fixed path, because the path is part of
  what a second process must agree on to hit.
  ``$ZNICZ_TPU_COMPILE_CACHE=off`` (the test suite's switch) disables
  the cache, but only where jax's variable is unset.
- :func:`ensure` is the idempotent boot hook called from
  ``Workflow.run``, ``FusedTrainStep.initialize`` and the serve plane's
  backend load — anywhere compiles are about to happen.  It never
  *imports* jax: a numpy-device run stays jax-free, and the next
  ensure() after jax appears finishes the job.
- every cache consultation lands in the metrics registry
  (``znicz_compile_cache_hits_total`` / ``_misses_total`` via
  ``observe.probe.compile_cache_event``), so warm-vs-cold is a counter
  delta — asserted by tests and the ``compile_latency`` bench scenario,
  not inferred from wall-clock jitter.  The miss counter also feeds
  ``watchtower.recompile_storm(metric="znicz_compile_cache_misses_
  total")``.
- failure paths degrade, never crash: an uncreatable directory logs a
  warning and leaves caching off; ``jax_raise_persistent_cache_errors``
  is pinned False so a corrupt entry at runtime is a logged cache miss.

The entry-size/compile-time thresholds default to 0 (JAX's defaults
skip sub-second compiles, which is every program in this repo's CPU
test geometry — a warm serve boot would then hit nothing).  Production
TPU programs clear the default thresholds anyway; see docs/COMPILE.md.
"""

from __future__ import annotations

import contextlib
import logging
import os
import sys
import threading
from typing import Optional

from znicz_tpu.core.config import CHECKOUT

#: jax's own variable: when set it IS the cache directory, from outside
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when nobody placed it; one directory is safely
#: shared by concurrent processes — entries are content-hashed and
#: written atomically by jax
DEFAULT_DIR = os.path.join(CHECKOUT, ".data", "cache", "jax")

#: the off switch (""/"off"/"none"/"0"); honoured only where
#: $JAX_COMPILATION_CACHE_DIR is unset
ENV_VAR = "ZNICZ_TPU_COMPILE_CACHE"
_OFF = ("", "off", "none", "0")

#: environment override for the minimum-compile-seconds threshold
ENV_MIN_S = "ZNICZ_TPU_COMPILE_CACHE_MIN_S"

_log = logging.getLogger("znicz_tpu.compilecache")

_lock = threading.Lock()
_configured = False                 # a configure() decision was made
_active_dir: Optional[str] = None   # the enabled directory, or None
_active_min_s: Optional[float] = None  # the applied threshold, or None
_listener_registered = False


def _resolve_dir(explicit: Optional[str]) -> Optional[str]:
    """$JAX_COMPILATION_CACHE_DIR, else the caller's directory, else the
    off switch, else the checkout's; ``None`` means caching is off."""
    placed = os.environ.get(JAX_ENV_VAR)
    if placed:
        return placed
    if explicit is not None:
        return None if str(explicit).lower() in _OFF else str(explicit)
    if os.environ.get(ENV_VAR, "on").lower() in _OFF:
        return None
    return DEFAULT_DIR


def _resolve_min_s(explicit: Optional[float]) -> float:
    """Minimum-compile-seconds threshold; a malformed env value is a
    warned-about 0, never a crash (the degrade contract)."""
    if explicit is not None:
        return float(explicit)
    raw = os.environ.get(ENV_MIN_S, "0")
    try:
        return float(raw)
    except ValueError:
        _log.warning("%s=%r is not a number; using 0", ENV_MIN_S, raw)
        return 0.0


#: the four durations jax 0.9.0 records while it makes a program, and
#: the phase each feeds (``probe.compile_phase``).  ``backend_compile``
#: is measured around ``compiler.compile_or_get_cached``, so on a cache
#: hit it CONTAINS the retrieval that ``cache_load`` reports (read in
#: ``jax/_src/interpreters/pxla.py`` and ``compiler.py``): a reader
#: subtracts.  The trace event fires for every nested ``jit`` as well,
#: cached ones included, each inside its caller's duration.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_PHASE_OF = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class _PhaseListener:
    """jax's duration events into ``probe.compile_phase``, with the
    nesting taken out of ``trace``: jax announces a trace's start as a
    scalar event, so a depth per thread tells the outermost trace from
    the thousands inside it, and what was lowered or compiled while it
    ran (an eager operation on a constant) comes off its seconds."""

    def __init__(self, sink) -> None:
        self._sink = sink
        self._tl = threading.local()

    def on_scalar(self, name: str, value, **kwargs) -> None:
        if name == _TRACE_EVENT:
            tl = self._tl
            depth = getattr(tl, "depth", 0)
            if not depth:
                tl.inside = 0.0
            tl.depth = depth + 1

    def on_duration(self, name: str, dt_s: float, **kwargs) -> None:
        phase = _PHASE_OF.get(name)
        if phase is None:
            return
        tl = self._tl
        depth = getattr(tl, "depth", 0)
        if phase == "trace":
            if depth > 1:
                tl.depth = depth - 1
                return
            tl.depth = 0
            dt_s = max(dt_s - getattr(tl, "inside", 0.0), 0.0)
        elif depth and phase != "cache_load":
            tl.inside += dt_s
        self._sink(phase, dt_s, str(kwargs.get("fun_name", "")))


def _register_listener() -> None:
    """Feed jax's cache-hit/miss and compile-duration monitoring events
    into the registry — once per process, regardless of later
    reconfiguration and of whether a cache directory is in use."""
    global _listener_registered
    if _listener_registered:
        return
    import jax._src.monitoring as _monitoring

    from znicz_tpu.observe import probe

    def _on_event(name: str, **kwargs) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            probe.compile_cache_event("hit")
        elif name == "/jax/compilation_cache/cache_misses":
            probe.compile_cache_event("miss")

    phases = _PhaseListener(probe.compile_phase)
    _monitoring.register_event_listener(_on_event)
    _monitoring.register_scalar_listener(phases.on_scalar)
    _monitoring.register_event_duration_secs_listener(phases.on_duration)
    _listener_registered = True


def _reset_jax_cache_state() -> None:
    """jax latches whether-the-cache-is-used ONCE per process (and pins
    the backing store to the directory live at first use) — so a
    configure() that changes the decision after any compile already
    happened must make jax forget, or the new directory is silently
    never consulted (the first tier-1 compiles run with the cache off,
    which is exactly how this was found)."""
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as _jax_cc)

        _jax_cc.reset_cache()
    except Exception as exc:  # noqa: BLE001 — degrade, never crash
        _log.debug("jax compilation-cache state reset unavailable: %r",
                   exc)


def _turn_off(jax) -> None:
    """Stop consulting a previously enabled directory — reached only
    where $JAX_COMPILATION_CACHE_DIR is unset, the one case in which
    this module may write an empty directory into the config."""
    global _configured, _active_dir, _active_min_s
    jax.config.update("jax_compilation_cache_dir", "")
    _reset_jax_cache_state()
    _configured, _active_dir, _active_min_s = True, None, None


def configure(cache_dir: Optional[str] = None,
              min_compile_time_s: Optional[float] = None,
              force: bool = False) -> Optional[str]:
    """Resolve + enable (or disable) the persistent compilation cache.

    Returns the active cache directory, or ``None`` when caching is
    off (explicitly, or because the directory could not be created —
    the degraded path is a warning, never an exception).  Idempotent:
    a second call is a no-op unless ``force`` or the arguments changed
    the resolution.  ``cache_dir`` is for callers that own a directory
    (tests, the cold/warm probes); ``$JAX_COMPILATION_CACHE_DIR``
    outranks it."""
    global _configured, _active_dir, _active_min_s
    with _lock:
        target = _resolve_dir(cache_dir)
        min_s = _resolve_min_s(min_compile_time_s)
        if (_configured and not force and target == _active_dir
                and (target is None or min_s == _active_min_s)):
            return _active_dir
        import jax

        _register_listener()
        if target is None:
            _turn_off(jax)
            _log.info("persistent compilation cache disabled")
            return None
        if target != os.environ.get(JAX_ENV_VAR):
            # a directory of our own choosing must prove usable; one
            # placed from outside is jax's to create and to complain
            # about, and is never replaced
            try:
                os.makedirs(target, exist_ok=True)
                probe_path = os.path.join(target, ".znicz_writable")
                with open(probe_path, "w"):
                    pass
                os.remove(probe_path)
            except OSError as exc:
                # graceful degradation (ISSUE 7 acceptance): every
                # compile is a logged miss, nothing crashes
                _log.warning("compile cache dir %r unusable (%s); "
                             "persistent caching disabled — all compiles "
                             "will be cold", target, exc)
                _turn_off(jax)
                return None
        jax.config.update("jax_enable_compilation_cache", True)
        if jax.config.jax_compilation_cache_dir != target:
            jax.config.update("jax_compilation_cache_dir", target)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # a corrupt/truncated entry must be a miss, not a crash
        jax.config.update("jax_raise_persistent_cache_errors", False)
        _reset_jax_cache_state()
        _configured, _active_dir, _active_min_s = True, target, min_s
        _log.info("persistent compilation cache at %s "
                  "(min_compile_time_s=%g)", target, min_s)
        return target


def ensure() -> Optional[str]:
    """Idempotent boot hook: configure the cache with layered defaults
    the first time compiles are about to happen.  A process that never
    imported jax is left untouched (a numpy-device workflow run must
    not boot a backend just to configure a cache it will never use)."""
    if _configured:
        return _active_dir
    if "jax" not in sys.modules:
        return None
    return configure()


@contextlib.contextmanager
def suspended():
    """Take the persistent cache out of the loop for a block, process-
    wide and atomically (the module lock is held throughout, so a
    concurrent configure()/ensure() cannot re-enable it mid-block).
    ``attach_aot`` needs this: serializing an executable that came out
    of ANY cache drops its object code, so its compiles must be fresh.
    Compiles on OTHER threads during the block run cold too — that is
    the cost of a process-global jax config."""
    if "jax" not in sys.modules:
        yield
        return
    import jax

    with _lock:
        prev = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", "")
        _reset_jax_cache_state()
        try:
            yield
        finally:
            jax.config.update("jax_compilation_cache_dir", prev or "")
            _reset_jax_cache_state()


def active_dir() -> Optional[str]:
    """The enabled cache directory, or None (off / not yet configured)."""
    return _active_dir


def stats() -> dict:
    """Cache state + lifetime hit/miss counters (the ``compile_latency``
    bench and the serve warmup summary read the deltas)."""
    from znicz_tpu.observe import probe

    hits, misses = probe.compile_cache_stats()
    return {"dir": _active_dir, "configured": _configured,
            "hits": hits, "misses": misses}


def _reset_for_tests() -> None:
    """Forget the configure() decision so tests can re-resolve; the
    monitoring listener stays registered (it is append-only in jax)."""
    global _configured, _active_dir, _active_min_s
    with _lock:
        _configured, _active_dir, _active_min_s = False, None, None
