"""Automatic instrumentation hooks wiring the runtime into the registry
and tracer (ISSUE 5 tentpole, part 3).

The production code calls these at fixed sites, mirroring the
resilience plane's ``fault_hook`` discipline:

====================  =====================================================
hook                  call site
====================  =====================================================
``unit_observers``    ``core/units.py :: Unit._timed_run`` — donates
                      per-unit run counts/seconds into
                      ``znicz_unit_runs_total`` / ``znicz_unit_run_
                      seconds_total`` (labels: workflow, unit); the
                      registry children ARE what ``timing_table()`` reads
``watch_compiles`` /  ``parallel/step.py`` registers its jitted
``check_recompiles``  functions; the workflow loop polls their
                      ``_cache_size()`` sum — a positive delta increments
                      ``znicz_recompiles_total{fn}`` and drops a
                      ``compile.recompile`` instant on the trace timeline
``staged_bytes``      ``pipeline/prefetcher.py`` worker — H2D staging
                      volume (counter) + per-pipeline live gauges
``resilience_event``  ``resilience/{faults,retry,supervisor,health}.py``
                      — every fault firing / retry / restart / NaN-guard
                      action lands as a counter increment AND an instant
                      event, so failures correlate with steps on one
                      timeline
``compile_cache_      ``compilecache.py``'s jax monitoring listener —
event``               every persistent compilation-cache consultation
                      lands in ``znicz_compile_cache_{hits,misses}_
                      total`` so warm-vs-cold boot is a counter delta
``compile_phase``     the same listener, from the compiler's own clock —
                      trace, lowering, backend compile and the cached
                      executable's load, each into ``znicz_compile_phase_
                      seconds_total{phase}`` and a ``compile.<phase>`` span
``setup_phase``       ``launcher.py`` (``load``, ``initialize``), the step
                      units (``init_params``, ``place``) and ``core/
                      backends.py`` (``backend``) — a live ``setup.<phase>``
                      span and ``znicz_setup_seconds{phase}``
====================  =====================================================

All hooks early-out on ``observe.set_enabled(False)`` (one module-global
load), which is how the ``metrics_overhead`` bench measures the bare
path and how determinism tests pin "instrumentation off == seed path".
"""

from __future__ import annotations

import collections
import contextlib
import functools
import re
import time
import weakref
from typing import NamedTuple, Optional

from znicz_tpu.observe import registry as _reg
from znicz_tpu.observe import trace as _trace

# -- enable/disable (module-global; also flips the tracer) -------------------

_enabled = True


def enabled() -> bool:
    return _enabled


def set_enabled(flag: bool) -> None:
    """Master switch for every automatic probe AND the global tracer —
    registry families stay registered (their values simply stop moving),
    so a scrape during a disabled window still parses."""
    global _enabled
    _enabled = bool(flag)
    if flag:
        _trace.TRACER.enable()
    else:
        _trace.TRACER.disable()


# -- workflow plane ----------------------------------------------------------

_UNIT_RUNS = _reg.counter(
    "znicz_unit_runs_total", "control-graph unit firings",
    labelnames=("workflow", "unit"))
_UNIT_SECONDS = _reg.counter(
    "znicz_unit_run_seconds_total", "wall seconds inside unit.run()",
    labelnames=("workflow", "unit"))
_STEP_SECONDS = _reg.histogram(
    "znicz_workflow_step_seconds",
    "wall time of one control-graph signal delivery")
_SIGNALS = _reg.counter(
    "znicz_workflow_signals_total", "control-graph signals dispatched")
_WORKFLOW_RUNS = _reg.counter(
    "znicz_workflow_runs_total", "Workflow.run invocations",
    labelnames=("workflow",))


def unit_observers(workflow_name: str, unit_name: str):
    """(runs_counter, seconds_counter) children for one unit — cached by
    the unit itself so the hot path is one :func:`unit_run` call."""
    return (_UNIT_RUNS.labels(workflow=workflow_name, unit=unit_name),
            _UNIT_SECONDS.labels(workflow=workflow_name, unit=unit_name))


def unit_run(obs, dt_s: float) -> None:
    """Donate one unit firing: both children share the registry lock, so
    taking it ONCE for the pair halves the hot-path lock traffic (the
    metrics_overhead budget is per-microsecond at signal granularity)."""
    runs, secs = obs
    with runs._lock:
        runs.value += 1.0
        secs.value += dt_s


def unit_timing_rows(workflow_name: str, unit_names) -> list:
    """``timing_table()``'s data source: ``(seconds, runs, unit)`` rows
    from the registry for one workflow's units.  Counters are
    process-lifetime (Prometheus semantics), so a supervised restart's
    table shows the CUMULATIVE cost across attempts — by design: that is
    the number a restart storm inflates.  Units sharing a name merge."""
    rows = []
    for name in dict.fromkeys(unit_names):          # dedupe, keep order
        runs = _UNIT_RUNS.labels(workflow=workflow_name, unit=name).get()
        secs = _UNIT_SECONDS.labels(workflow=workflow_name,
                                    unit=name).get()
        rows.append((secs, int(runs), name))
    return rows


def signal_dispatched(dt_s: float) -> None:
    """One control-graph delivery took ``dt_s`` wall seconds.  Only the
    histogram moves per signal; ``znicz_workflow_signals_total`` is
    batch-incremented per run (:func:`signals_add`) — one fewer lock
    round-trip on the per-signal path."""
    _STEP_SECONDS.observe(dt_s)


def signals_add(n: int) -> None:
    """Batch-donate ``n`` dispatched signals (called once per
    Workflow.run with the walk's delta)."""
    if n:
        _SIGNALS.inc(n)


def workflow_run(workflow_name: str) -> None:
    _WORKFLOW_RUNS.labels(workflow=workflow_name).inc()


# -- recompile detection -----------------------------------------------------

_RECOMPILES = _reg.counter(
    "znicz_recompiles_total",
    "XLA compile-cache growth observed on watched jitted functions",
    labelnames=("fn",))

#: key -> [tuple of weakrefs to jitted fns, last observed cache-size
#: sum, metric label].  Weak refs: a watched step that dies (dropped
#: workflow, supervised-restart rebuild) stops being polled and its
#: entry is reaped on the next poll, so two live steps never fight over
#: one key and dead ones never pin their compiled programs in memory.
_watched: dict[str, list] = {}


def watch_compiles(key: str, *fns, label: Optional[str] = None) -> None:
    """Register jitted function(s) for compile-cache delta polling.
    ``key`` must be unique per watched OBJECT (two live FusedTrainSteps
    in one process each keep their own watch); ``label`` is the
    ``znicz_recompiles_total{fn=...}`` label and defaults to ``key`` —
    instances of one class share a label while keeping separate
    baselines.  Functions without ``_cache_size`` (older jax, non-jit
    callables) are ignored.  A warm function registers its current
    cache size as the baseline, so only growth counts."""
    refs = tuple(weakref.ref(f) for f in fns
                 if hasattr(f, "_cache_size"))
    if not refs:
        return
    _watched[key] = [refs, _cache_total(refs), label or key]


def unwatch_compiles(key: str) -> None:
    _watched.pop(key, None)


def _cache_total(refs) -> Optional[int]:
    """Cache-size sum over the still-living functions; None when every
    ref is dead (the entry should be reaped)."""
    total, alive = 0, False
    for ref in refs:
        fn = ref()
        if fn is None:
            continue
        alive = True
        try:
            total += int(fn._cache_size())
        except Exception:  # noqa: BLE001 — a torn-down backend must not
            pass           # crash the run loop polling it
    return total if alive else None


def check_recompiles() -> int:
    """Poll watched functions; returns newly observed compiles.  The
    FIRST compile of a fresh function counts too — a steady-state loop
    asserts the counter moves exactly once per function, and the pinned
    zero-recompile tests keep holding because they compare cache sizes
    directly."""
    if not _watched or not _enabled:
        return 0
    new = 0
    for key, entry in list(_watched.items()):
        total = _cache_total(entry[0])
        if total is None:                 # every watched fn died
            _watched.pop(key, None)
            continue
        delta = total - entry[1]
        if delta > 0:
            entry[1] = total
            new += delta
            _RECOMPILES.labels(fn=entry[2]).inc(delta)
            _trace.instant("compile.recompile", fn=entry[2], new=delta,
                           cache_size=total)
        elif delta < 0:
            # a subset of the fns died (or a cache was cleared): rebase
            # so the shrink is not later mistaken for absence of growth
            entry[1] = total
    return new


# -- cold-compile timing -----------------------------------------------------

_COMPILE_SECONDS = _reg.histogram(
    "znicz_compile_seconds",
    "cold-path XLA compile wall time: first call of a wrapped jitted "
    "program, or a serve-engine bucket materializing",
    labelnames=("fn",))


def compile_observed(label: str, dt_s: float, **args) -> None:
    """One cold compile (+ first execution) took ``dt_s`` wall seconds:
    histogram observation plus a ``compile.cold`` complete-span on the
    trace timeline (and in :data:`SETUP_RING`), so the ROADMAP
    compile-latency work lands with its baseline already recorded."""
    if not _enabled:
        return
    _COMPILE_SECONDS.labels(fn=label).observe(dt_s)
    _setup_event("compile.cold", time.perf_counter() - dt_s, dt_s,
                 {"fn": label, **args})


# -- set-up in its parts (ISSUE 37) -------------------------------------------

#: the set-up events once more, in a ring of their own that nobody clears:
#: ``setup.<phase>``, ``compile.<phase>`` and ``compile.cold`` with their
#: stamps, on :data:`trace.TRACER`'s clock.  A caller that clears the main
#: ring when its measurement begins (the benchmark does) still finds here
#: WHEN each part of the set-up ran, which a counter cannot say.
SETUP_RING = _trace.Tracer(capacity=8192, origin=_trace.TRACER._origin)

_COMPILE_PHASE = _reg.counter(
    "znicz_compile_phase_seconds_total",
    "seconds jax spent making programs, by the compiler's own clock: "
    "trace (outermost traces only, less what they compiled inside), "
    "lower (jaxpr to MLIR), backend_compile (XLA's compile or the "
    "persistent cache's lookup and load: it contains cache_load) and "
    "cache_load (the retrieval of a cached executable)",
    labelnames=("phase",))
_SETUP_SECONDS = _reg.gauge(
    "znicz_setup_seconds",
    "wall seconds of the set-up's phases so far: load (the workflow's "
    "builder), initialize (Workflow.initialize) and inside it "
    "init_params (weights drawn on the host), place (parameters, "
    "optimizer state and a pinned dataset put on the device, fenced) "
    "and backend (the first jax client start, where the program made it)",
    labelnames=("phase",))

#: compile-phase events shorter than this stay out of the rings (a trace
#: visits thousands of cached sub-traces); the counter takes them all
MIN_PHASE_SPAN_S = 1e-3


def _setup_event(name: str, start: float, dt_s: float,
                 args: Optional[dict] = None) -> None:
    """One already-timed set-up event into both rings."""
    _trace.TRACER.complete(name, start, dt_s, args)
    SETUP_RING.complete(name, start, dt_s, args)


def compile_phase(phase: str, dt_s: float, fn: str = "") -> None:
    """``dt_s`` seconds of one compile phase (``trace`` | ``lower`` |
    ``backend_compile`` | ``cache_load``), as jax's monitoring reported
    it to ``compilecache``'s listener: the counter, and a
    ``compile.<phase>`` span that ends now."""
    if not _enabled:
        return
    _COMPILE_PHASE.labels(phase=phase).inc(dt_s)
    if dt_s >= MIN_PHASE_SPAN_S:
        _setup_event(f"compile.{phase}", time.perf_counter() - dt_s, dt_s,
                     {"fn": fn} if fn else None)


def placed(tree):
    """``tree`` once the device holds it: the fence at the end of a
    ``setup.place`` span, so that its seconds are the transfer's.  Taken
    only while the span is (a transfer otherwise overlaps what follows)."""
    if _enabled:
        import jax

        jax.block_until_ready(tree)
    return tree


@contextlib.contextmanager
def setup_phase(phase: str):
    """A live ``setup.<phase>`` span (ring, the profiler's host plane
    under ``--profile``, :data:`SETUP_RING`) whose seconds also add to
    ``znicz_setup_seconds{phase}``."""
    if not _enabled:
        yield
        return
    with _trace.TRACER.timed(f"setup.{phase}") as span:
        yield
    _SETUP_SECONDS.labels(phase=phase).inc(span.dt)
    SETUP_RING.complete(f"setup.{phase}", span.t0, span.dt)


#: every live :class:`_CompileTimed`, for :func:`scope_map`
_timed_programs: "weakref.WeakSet" = weakref.WeakSet()
#: the newest of them, held: a workflow's units form a reference cycle, so
#: once its owner lets go the programs live until the next cycle
#: collection, and a scope_map() asked for after the run found them or not
#: by that chance (one traced run in about seven read no scopes, PR 25)
_recent_programs: collections.deque = collections.deque(maxlen=32)


class _CompileTimed:
    """Thin wrapper over a jitted callable: the FIRST invocation — the
    trace+compile+run cold path — is timed into ``znicz_compile_seconds
    {fn=label}``; every later call is one attribute check of passthrough.
    ``_cache_size`` delegates so :func:`watch_compiles` keeps polling the
    real compile cache through the wrapper.  The first call's argument
    shapes, dtypes and shardings are remembered (no buffer is kept), so
    :func:`scope_table` can lower the same program again when asked."""

    __slots__ = ("_fn", "_label", "_cold", "_abstract", "_table",
                 "__weakref__")

    def __init__(self, fn, label: str) -> None:
        self._fn = fn
        self._label = label
        self._cold = True
        self._abstract = None
        #: (the signature parsed, module, rows), by :func:`scope_table`
        self._table = None
        _timed_programs.add(self)
        _recent_programs.append(self)

    def _cache_size(self) -> int:
        size = getattr(self._fn, "_cache_size", None)
        return int(size()) if size is not None else 0

    def lower(self, *args, **kw):
        """The wrapped program's ``jit(...).lower``."""
        return self._fn.lower(*args, **kw)

    def __call__(self, *args, **kw):
        if not self._cold:
            return self._fn(*args, **kw)
        self._cold = False
        if hasattr(self._fn, "lower"):
            self._abstract = _abstract_call(args, kw)
        t0 = time.perf_counter()
        out = self._fn(*args, **kw)
        compile_observed(self._label, time.perf_counter() - t0)
        return out


def time_compiles(label: str, fn):
    """Wrap ``fn`` (a jitted program) so its first call lands in the
    compile-time histogram; ``None`` passes through for optional
    programs."""
    if fn is None:
        return None
    return _CompileTimed(fn, label)


# -- named scopes and the scope map (ISSUE 24) -------------------------------

#: scope names the program has opened through :func:`scope`
_scope_names: set = set()
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = .*?[})\]] ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"(calls|to_apply|body|condition)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
#: a compiler option at its default: changes nothing but jit's memo
_FRESH_COMPILE = {"xla_embed_ir_in_executable": False}
#: what marks a ``custom-call`` as a Mosaic (Pallas) kernel
_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
#: instructions that are no work of their own
TRIVIAL_OPCODES = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota"))


def scope(name: str):
    """``jax.named_scope(name)``, with the name remembered so that
    :func:`scope_map` can find it in an operation's ``op_name``.
    Metadata only: what the program computes does not change."""
    import jax

    _scope_names.add(name)
    return jax.named_scope(name)


def scope_bwd(name: str):
    """The label AD gives the backward operations of scope ``name``
    (``transpose(jvp(name))``), for a backward pass written by hand (a
    ``custom_vjp`` rule): its operations would otherwise carry no scope,
    or the forward pass's."""
    import jax

    _scope_names.add(name)
    return jax.named_scope(f"transpose(jvp({name}))")


def scoped(name: str):
    """Decorator: the body of the function runs under :func:`scope`."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with scope(name):
                return fn(*args, **kw)
        return wrapper
    return deco


def _abstract_call(args, kw):
    """Shapes, dtypes and shardings of one call's arguments."""
    import jax

    def leaf(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None))
        return a
    return jax.tree.map(leaf, (args, kw))


def _bare(component: str) -> str:
    """``transpose(jvp(conv.00_c))`` -> ``conv.00_c``."""
    return component.rstrip(")").rsplit("(", 1)[-1]


def scope_of(op_name: str, names=None) -> str:
    """The program's scope in one ``op_name`` path: its outermost
    component that is a name opened through :func:`scope`, kept as it
    stands there -- ``conv.00_Conv`` in an eval pass, ``jvp(conv.00_
    Conv)`` in a differentiated forward, ``transpose(jvp(conv.00_
    Conv))`` in the backward pass -- or ``""`` under none.  This is all
    the projection (:func:`scope_map`) keeps of a path: a scope opened
    inside another, and a ``transpose(`` or ``rematted_computation``
    that stands on an ancestor (a scan's, a checkpoint's), are the
    table's (:func:`path_of`, :func:`way_of`)."""
    names = _scope_names if names is None else names
    for part in op_name.split("/"):
        if _bare(part) in names:
            return part
    return ""


def path_of(op_name: str, names=None) -> tuple:
    """Every scope of one ``op_name`` path that was opened through
    :func:`scope` / :func:`scope_bwd`, outermost to innermost, as bare
    names: ``("block3.ssm", "block3.ssm.gate")``."""
    names = _scope_names if names is None else names
    return tuple(b for b in map(_bare, op_name.split("/")) if b in names)


def way_of(op_name: str) -> str:
    """The pass one ``op_name`` path belongs to: ``remat`` where a
    component is ``rematted_computation`` (a checkpoint's forward made
    again; it stands under a ``transpose(`` ancestor), ``bwd`` where ANY
    other path has a component that starts with ``transpose(`` -- the
    scope's own (``transpose(jvp(conv.00_c))``, :func:`scope_bwd`'s
    literal label) or an ancestor's (a scan's ``transpose(jvp())/while/
    body``, a checkpoint's ``transpose(jvp(jvp()))/checkpoint``) -- else
    ``fwd``."""
    parts = op_name.split("/")
    if "rematted_computation" in parts:
        return "remat"
    if any(p.startswith("transpose(") for p in parts):
        return "bwd"
    return "fwd"


class ScopeRow(NamedTuple):
    """What :func:`parse_scopes` knows of one instruction."""

    #: the projection: :func:`scope_of` of the ``op_name`` the scope came
    #: from (its own, its fusion's root's, a neighbour's), ``""`` under none
    scope: str
    #: :func:`path_of` of that same ``op_name``
    path: tuple
    #: ``fwd`` | ``remat`` | ``bwd``: :func:`way_of` of that ``op_name``,
    #: or, of an instruction whose scope is a neighbour's or nobody's, of
    #: its own ``op_name`` where it has one
    way: str
    #: where the scope came from: ``own`` (it stands in the instruction's
    #: ``op_name``), ``root`` / ``inside`` (a fusion without one: the root
    #: of the computation it calls, else the first scoped instruction in
    #: it), ``lent`` (a neighbour's), ``none``
    how: str
    #: a fusion whose fused instructions carry more than one
    #: ``(outermost scope, way)``
    mixed: bool
    #: ``kernel`` (a Mosaic ``custom-call``), ``product`` (it, or the
    #: computation it calls, holds a ``convolution`` / ``dot``), ``stack``
    #: (a ``dynamic-update-slice`` / ``dynamic-slice`` under ``while/body``
    #: that stands under no scope, or a scopeless fusion around one),
    #: ``copy``, ``pad``, ``other``
    holds: str
    #: ``(outermost scope, way)`` by what the instruction does: of the
    #: products a fusion holds where they are all one scope's, else of
    #: ``path`` and ``way``; of a ``stack`` instruction the scope of the
    #: value it writes (the update's producer) or reads (the slice's
    #: consumers), which no order of the text moves
    by_work: tuple

    @property
    def moved(self) -> bool:
        """``by_work`` names another scope than the projection does."""
        return self.by_work[0] != (self.path[0] if self.path else "")


_NO_OP = ("", (), "fwd")
_PRODUCTS = ("convolution", "dot")
_STACKS = ("dynamic-update-slice", "dynamic-slice")


def _operand_text(line: str, start: int) -> str:
    """The operand list that opens at ``line[start]``, to its ``)``."""
    depth = 1
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if not depth:
                return line[start:i]
    return line[start:]


def _commonest(scopes) -> str:
    """The scope most of ``scopes`` name; the first by name on a tie."""
    counts = collections.Counter(s for s in scopes if s)
    return min(counts, key=lambda s: (-counts[s], s)) if counts else ""


def parse_scopes(hlo_text: str, names=None, rows: bool = False) -> tuple:
    """``(module name, {instruction name: scope})`` from one optimised
    HLO module's text, or, with ``rows``, ``{instruction name:``
    :class:`ScopeRow` ``}`` from the same one parse: the first is the
    second's ``scope`` column.  An instruction takes, in this order: the
    scope in its own ``op_name``; that of the root of the computation it
    calls (a fusion), else of any instruction in it; and, where the
    compiler gave it no metadata (a layout ``copy``, an async slice or
    copy, a scalar it moved), the scope of the first instruction that
    consumes it, else of an operand -- whose layout the copy is -- else of
    the instruction that calls its computation.  Trivial instructions
    (:data:`TRIVIAL_OPCODES`) pass scopes on but are left out; ``""`` is
    what remains under no scope.

    The projection keeps the outermost scope's component as it stands
    (:func:`scope_of`) and says nothing of the rule that gave it.  A row
    adds every scope of the path, the pass read off ANY component of it,
    which rule gave the scope (the first-neighbour rule follows the order
    of the text, so ``lent`` time is what a group may gain or lose with no
    work moved), what the instruction holds, and ``by_work``, an
    attribution that looks at the work and not at the text's order."""
    module, comp = "", None
    scope: dict = {}          # instruction -> scope
    given: dict = {}          # instruction -> (scope, path, way) it took
    how: dict = {}            # instruction -> own | root | inside | lent
    own_way: dict = {}        # instruction with an op_name -> its way
    operands: dict = {}       # instruction -> [operand names]
    n_args: dict = {}         # instruction -> how many of them are operands
    arg_text: dict = {}       # parameter -> its number, as text
    opcode_of: dict = {}
    comp_of: dict = {}        # instruction -> its computation
    members: dict = {}        # computation -> [its instructions]
    root_of: dict = {}        # computation -> its root
    fused: dict = {}          # instruction -> the computation it calls=
    fusion_of: dict = {}      # computation -> the fusion that calls= it
    caller: dict = {}         # computation -> an instruction that calls it
    roots: dict = {}          # computation -> scope of its root
    inside: dict = {}         # computation -> first scope found in it
    inside_of: dict = {}      # computation -> the instruction that had it
    kernels: set = set()      # Mosaic custom-calls
    bare_stacks: set = set()  # stack instructions under no scope
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        is_root, name, opcode = m.groups()
        op = _OP_NAME.search(line)
        sc = scope_of(op.group(1), names) if op else ""
        if op:
            own_way[name] = way_of(op.group(1))
        if is_root:
            roots[comp], root_of[comp] = sc, name
        if sc:
            given[name] = (sc, path_of(op.group(1), names), own_way[name])
            how[name] = "own"
            if comp not in inside:
                inside[comp], inside_of[comp] = sc, name
        elif op and opcode in _STACKS and \
                "/while/body/" in op.group(1) + "/":
            bare_stacks.add(name)
        for key, callee in _CALLED.findall(line):
            caller.setdefault(callee, name)
            if key == "calls":
                fused[name] = callee
                fusion_of[callee] = name
        scope[name], opcode_of[name], comp_of[name] = sc, opcode, comp
        members.setdefault(comp, []).append(name)
        operands[name] = _OPERAND.findall(line[m.end():])
        args = _operand_text(line, m.end())
        n_args[name] = len(_OPERAND.findall(args))
        if opcode == "parameter":
            arg_text[name] = args
        elif opcode == "custom-call" and _MOSAIC_CALL in line:
            kernels.add(name)
    for name, called in fused.items():
        if not scope[name]:
            scope[name] = roots.get(called) or inside.get(called, "")
            if scope[name]:
                rule = "root" if roots.get(called) else "inside"
                src = (root_of if rule == "root" else inside_of)[called]
                given[name], how[name] = given[src], rule
    users: dict = {}
    for name, ops in operands.items():
        for o in ops:
            if o in scope:
                users.setdefault(o, []).append(name)

    def neighbour(name):
        # consumers first (whose layout a bare copy is), then operands,
        # then the instruction that calls this one's computation
        for n in (*users.get(name, ()), *operands.get(name, ()),
                  caller.get(comp_of[name])):
            if scope.get(n):
                return n
        return None

    # hand scopes down chains of bare instructions (copy-start ->
    # copy-done -> bitcast -> the fusion that reads it)
    for _ in range(8):
        moved = False
        for name, sc in scope.items():
            if not sc:
                got = neighbour(name)
                if got is not None:
                    scope[name], moved = scope[got], True
                    given[name], how[name] = given[got], "lent"
        if not moved:
            break
    kept = {n: sc for n, sc in scope.items()
            if opcode_of[n] not in TRIVIAL_OPCODES}
    if not rows:
        return module, kept

    def settled(name) -> str:
        """The outermost scope an instruction has by its own metadata or
        its fused computation's: nothing lent."""
        if how.get(name) in ("own", "root", "inside"):
            return given[name][1][0]
        return ""

    def args_of(name) -> list:
        return operands[name][:n_args[name]]

    def producer_scope(name) -> str:
        """Up from a value to the first instruction that has a scope."""
        for _ in range(16):
            if name not in opcode_of:
                return ""
            if settled(name):
                return settled(name)
            fusion = fusion_of.get(comp_of[name])
            if opcode_of[name] == "parameter" and fusion is not None:
                k = int(arg_text[name])
                if k >= n_args[fusion]:
                    return ""
                name = operands[fusion][k]
            elif n_args[name]:
                name = operands[name][0]
            else:
                return ""
        return ""

    def consumer_scope(name) -> str:
        """Down from a value to the instructions that read it: the scope
        most of them have."""
        found, front, seen = [], [name], {name}
        for _ in range(6):
            nxt = []
            for v in front:
                # what a fused computation's root holds, the fusion's
                # readers read
                fusion = fusion_of.get(comp_of[v])
                step = [fusion] if fusion is not None and \
                    v == root_of[comp_of[v]] else []
                for u in users.get(v, ()):
                    if v not in args_of(u):
                        continue
                    if settled(u):
                        found.append(settled(u))
                    else:
                        step.append(u)
                nxt += [u for u in step if u not in seen]
                seen.update(step)
            if found or not nxt:
                break
            front = nxt
        return _commonest(found)

    def stack_value(name) -> str:
        if opcode_of[name] == "dynamic-update-slice":
            return producer_scope(args_of(name)[1]) \
                if n_args[name] > 1 else ""
        return consumer_scope(name)

    table: dict = {}
    for name, sc in kept.items():
        opcode, called = opcode_of[name], fused.get(name)
        inner = members.get(called, ()) if called else ()
        _, path, way = given.get(name, _NO_OP)
        rule = how.get(name, "none")
        if rule in ("lent", "none") and name in own_way:
            way = own_way[name]
        held, made = set(), set()     # (scope, way) inside; of products
        for i in inner:
            if how.get(i) == "own":
                held.add((given[i][1][0], given[i][2]))
                if opcode_of[i] in _PRODUCTS:
                    made.add((given[i][1][0], given[i][2]))
        stacks = [i for i in (name, *inner) if i in bare_stacks] \
            if rule != "own" else []
        first = re.split(r"[._]", name)[0]
        if name in kernels:
            holds = "kernel"
        elif opcode in _PRODUCTS or \
                any(opcode_of[i] in _PRODUCTS for i in inner):
            holds = "product"
        elif stacks:
            holds = "stack"
        else:
            holds = next((k for k in ("copy", "pad") if opcode == k or
                          opcode.startswith(k + "-") or first == k), "other")
        by_work = (path[0] if path else "", way)
        if len(made) == 1:
            by_work = next(iter(made))
        elif holds == "stack":
            value = _commonest(stack_value(i) for i in stacks)
            if value:
                by_work = (value, way)
        table[name] = ScopeRow(sc, path, way, rule, len(held) > 1, holds,
                               by_work)
    return module, table


def scope_table() -> dict:
    """``{module name: {instruction name:`` :class:`ScopeRow` ``}}`` for
    every program wrapped by :func:`time_compiles` that has run: each is
    lowered again from the remembered shapes of its first call, compiled,
    and its optimised HLO parsed by :func:`parse_scopes`; the rows are
    remembered with the program, so this and :func:`scope_map` cost one
    compile a program between them however often either is asked.

    The compile has to be a fresh one.  The executable that runs may
    have come out of the persistent cache, whose key leaves metadata
    out: it is then the same program as whichever commit compiled it
    first annotated it, with that commit's scopes or none (found on the
    chip, PERF.md PR 24), and jit hands the same executable back for the
    same lowering.  So the cache is suspended and a compiler option at
    its default is passed, which makes jit compile again.  Instruction
    names do not depend on metadata, so the fresh compile names them as
    the running executable does.  Built only when called: this is the
    profile reader's join from a device operation to the program's
    scope, and costs nothing (one compile per program, after the run)
    until a reader asks."""
    from znicz_tpu import compilecache

    out: dict = {}
    with compilecache.suspended():
        for prog in list(_timed_programs):
            if prog._abstract is None:
                continue
            if prog._table is None or prog._table[0] is not prog._abstract:
                args, kw = prog._abstract
                text = prog.lower(*args, **kw).compile(
                    compiler_options=_FRESH_COMPILE).as_text()
                prog._table = (prog._abstract,
                               *parse_scopes(text, rows=True))
            _, module, table = prog._table
            known = out.setdefault(module, {})
            for name, row in table.items():
                if row.scope or name not in known:  # two live steps, one name
                    known[name] = row
    return out


def scope_map() -> dict:
    """``{module name: {instruction name: scope}}``: the ``scope`` column
    of :func:`scope_table`, which is what every ``*_device_ms_per_step``
    reads."""
    return {module: {name: row.scope for name, row in table.items()}
            for module, table in scope_table().items()}


# -- persistent compilation cache (ISSUE 7) ----------------------------------

_CACHE_HITS = _reg.counter(
    "znicz_compile_cache_hits_total",
    "persistent XLA compilation-cache hits (an executable was loaded "
    "from disk instead of compiled)")
_CACHE_MISSES = _reg.counter(
    "znicz_compile_cache_misses_total",
    "persistent compilation-cache misses — cold compiles; feeds "
    "watchtower.recompile_storm when pointed at this family")


def compile_cache_event(kind: str) -> None:
    """One cache consultation, fed by ``compilecache``'s jax monitoring
    listener.  ``kind``: ``hit`` | ``miss``.  Counted even while probes
    are disabled: the warm-vs-cold contract (tests, the
    ``compile_latency`` bench, t1's zero-JIT smoke) must stay assertable
    through an ``observe.set_enabled(False)`` window, and a compile is
    not on any per-signal hot path."""
    (_CACHE_HITS if kind == "hit" else _CACHE_MISSES).inc()


def compile_cache_stats() -> tuple:
    """Lifetime ``(hits, misses)`` — scenario lines and the serve
    warmup summary report deltas of these."""
    return int(_CACHE_HITS.get()), int(_CACHE_MISSES.get())


# -- ZeRO sharding plane (ISSUE 15) ------------------------------------------

_ZERO_PARAM_BYTES = _reg.gauge(
    "znicz_zero_param_bytes",
    "per-chip bytes of persistent model parameters held by a fused "
    "train step (full when replicated; 1/n flat shards + padding under "
    "shard_params)", labelnames=("unit",))
_ZERO_OPT_BYTES = _reg.gauge(
    "znicz_zero_opt_state_bytes",
    "per-chip bytes of persistent optimizer/EMA state held by a fused "
    "train step (1/n flat shards under shard_update/shard_params)",
    labelnames=("unit",))
_ZERO_GATHERED = _reg.counter(
    "znicz_zero_gathered_bytes_total",
    "bytes all-gathered on demand to materialize full weights for a "
    "forward/backward dispatch under shard_params",
    labelnames=("unit",))


def zero_memory(unit: str, param_bytes: int, opt_bytes: int) -> None:
    """Per-chip persistent-state accounting, set once per step build.
    Recorded even while probes are disabled (the compile_cache_event
    precedent): the memory contract must stay assertable through a
    bench's bare arm, and a step build is never on the per-signal hot
    path."""
    _ZERO_PARAM_BYTES.labels(unit=unit).set(float(param_bytes))
    _ZERO_OPT_BYTES.labels(unit=unit).set(float(opt_bytes))


def zero_gather_counter(unit: str):
    """Cached child handle for the per-dispatch gathered-bytes counter
    (the step increments it on its hot path — one ``inc`` per dispatch,
    gated on :func:`enabled` by the caller)."""
    return _ZERO_GATHERED.labels(unit=unit)


# -- quantized collectives (ISSUE 18) ----------------------------------------

#: ``collective`` label values: "grad_psum" (the explicit gradient
#: reduction) and "zero_gather" (the shard_params regather chain)
_QCOMM_WIRE = _reg.counter(
    "znicz_qcomm_bytes_on_wire_total",
    "bytes actually shipped by quantized collectives (int8/bf16 payload "
    "+ per-chunk scales), per unit and collective site",
    labelnames=("unit", "collective"))
_QCOMM_EXACT = _reg.counter(
    "znicz_qcomm_bytes_exact_total",
    "bytes the SAME collectives would have shipped unquantized (f32) — "
    "the before to znicz_qcomm_bytes_on_wire_total's after",
    labelnames=("unit", "collective"))
_QCOMM_RATIO = _reg.gauge(
    "znicz_qcomm_compression_ratio",
    "exact/wire byte ratio of a quantized collective (~4 for int8 with "
    "the default chunk, 2 for bf16); set once per step build",
    labelnames=("unit", "collective"))
_QCOMM_RESIDUAL = _reg.gauge(
    "znicz_qcomm_residual_norm",
    "L2 norm of the error-feedback residual tree carried by a fused "
    "train step (quantization error deferred into the next step)",
    labelnames=("unit",))


def qcomm_ratio(unit: str, collective: str, wire_bytes: int,
                exact_bytes: int) -> None:
    """Static per-dispatch compression figure, set once per step build.
    Recorded even while probes are disabled (the zero_memory precedent:
    the wire contract must stay assertable through a bench's bare arm,
    and a build is never on the per-signal hot path)."""
    _QCOMM_RATIO.labels(unit=unit, collective=collective).set(
        float(exact_bytes) / max(float(wire_bytes), 1.0))


def qcomm_counters(unit: str, collective: str) -> tuple:
    """Cached ``(wire, exact)`` counter children for one collective site
    (the step increments both per dispatch, gated on :func:`enabled`)."""
    return (_QCOMM_WIRE.labels(unit=unit, collective=collective),
            _QCOMM_EXACT.labels(unit=unit, collective=collective))


def qcomm_residual_norm(unit: str, value: float) -> None:
    """Error-feedback residual L2 norm (published at class-pass ends —
    the caller owns the device reduction and the :func:`enabled` gate)."""
    _QCOMM_RESIDUAL.labels(unit=unit).set(float(value))


# -- pipeline plane ----------------------------------------------------------

_BYTES_STAGED = _reg.counter(
    "znicz_pipeline_bytes_staged_total",
    "host bytes shipped through prefetch stagers")


def staged_bytes(nbytes: int) -> None:
    if _enabled:
        _BYTES_STAGED.inc(nbytes)


# -- resilience plane --------------------------------------------------------

_RESILIENCE = _reg.counter(
    "znicz_resilience_events_total",
    "resilience-plane events (fault fired, retry, restart, hang, "
    "nan_guard, snapshot_resume)", labelnames=("kind", "site"))


def resilience_event(kind: str, site: str = "", **args) -> None:
    """Counter + same-timeline instant event for one resilience action.
    ``kind``: fault | retry | restart | hang | nan_guard |
    snapshot_resume; ``site`` is the fault-plan site / fn name / '' when
    not site-shaped."""
    if not _enabled:
        return
    _RESILIENCE.labels(kind=kind, site=site).inc()
    _trace.instant(f"resilience.{kind}", site=site, **args)


# -- elastic fleet (ISSUE 9) -------------------------------------------------

_ELASTIC_RESTARTS = _reg.counter(
    "znicz_elastic_restarts_total",
    "elastic fleet restart rounds (a worker died or hung; the remainder "
    "was killed and the fleet relaunched)")
_ELASTIC_DEATHS = _reg.counter(
    "znicz_elastic_worker_deaths_total",
    "worker processes observed dead without being asked to stop",
    labelnames=("cause",))
_ELASTIC_RESUMES = _reg.counter(
    "znicz_elastic_resumes_total",
    "fleet relaunches that resumed from a valid snapshot (vs cold "
    "restarts)")
_ELASTIC_WORLD = _reg.gauge(
    "znicz_elastic_world_size",
    "worker-process count of the currently running fleet round (0 when "
    "no fleet is up)")


def elastic_event(kind: str, **args) -> None:
    """One elastic-fleet lifecycle event: counter + timeline instant.
    ``kind``: restart | resume | worker_death (``cause`` = exit |
    signal | hung | boot | wedged).  Counted in the SUPERVISOR process
    — workers keep their own registries."""
    if not _enabled:
        return
    if kind == "worker_death":
        _ELASTIC_DEATHS.labels(cause=args.get("cause", "exit")).inc()
    elif kind == "restart":
        _ELASTIC_RESTARTS.inc()
    elif kind == "resume":
        _ELASTIC_RESUMES.inc()
    _trace.instant(f"elastic.{kind}", **args)


def elastic_world_size(n: int) -> None:
    """Gauge: the fleet's live world size (set at each round launch,
    zeroed when the fleet returns)."""
    _ELASTIC_WORLD.set(float(n))


def elastic_counts() -> dict:
    """Lifetime elastic counters — the drill asserts these match its
    event counts."""
    deaths = sum(child.get() for _, child in _ELASTIC_DEATHS.items())
    return {"restarts": int(_ELASTIC_RESTARTS.get()),
            "worker_deaths": int(deaths),
            "resumes": int(_ELASTIC_RESUMES.get()),
            "world_size": int(_ELASTIC_WORLD.get())}


# -- step anatomy (ISSUE 20) -------------------------------------------------


def anatomy_phase(plane: str, phase: str, dt_s: float,
                  t0: Optional[float] = None) -> None:
    """One already-timed anatomy phase from a producer that owns its
    own clock (prefetcher input-wait/stage, the continuous batcher's
    prefill/decode/verify).  Thin delegate so producers only import
    probe; the import is lazy to keep anatomy off probe's module-load
    path."""
    if not _enabled:
        return
    from znicz_tpu.observe import anatomy as _anatomy
    _anatomy.observe_phase(plane, phase, dt_s, t0=t0)


# -- goodput ledger (ISSUE 20; supervisor-side, like the elastic plane) ------

_GOODPUT_PRODUCTIVE = _reg.counter(
    "znicz_goodput_productive_seconds_total",
    "per-rank wall seconds the elastic fleet spent making step progress "
    "(completed rounds + failed-round time covered by a later-valid "
    "snapshot)", labelnames=("rank",))
_GOODPUT_LOST = _reg.counter(
    "znicz_goodput_lost_seconds_total",
    "per-rank wall seconds of work discarded by a failure (failed-round "
    "time past the newest valid snapshot — recomputed after restart)",
    labelnames=("rank",))
_GOODPUT_SNAPSHOT = _reg.counter(
    "znicz_goodput_snapshot_seconds_total",
    "per-rank wall seconds inside teardown/snapshot grace windows "
    "(SIGTERM grace, snapshot-then-exit)", labelnames=("rank",))
_GOODPUT_IDLE = _reg.counter(
    "znicz_goodput_idle_seconds_total",
    "per-rank wall seconds with no fleet running (spawn windows, "
    "restart backoff, flight dumps)", labelnames=("rank",))
_GOODPUT_RATIO = _reg.gauge(
    "znicz_goodput_ratio",
    "productive / (productive + lost + snapshot + idle) over the "
    "supervisor's lifetime — the fleet-level goodput figure")

_GOODPUT = {"productive": _GOODPUT_PRODUCTIVE, "lost": _GOODPUT_LOST,
            "snapshot": _GOODPUT_SNAPSHOT, "idle": _GOODPUT_IDLE}


def goodput_pretouch(ranks) -> None:
    """Materialize every goodput child before the first fleet sample
    (PR 11 delta-rule lesson — see ``anatomy.pretouch``)."""
    for rank in ranks:
        for fam in _GOODPUT.values():
            fam.labels(rank=str(rank)).inc(0.0)
    _GOODPUT_RATIO.set(0.0)


def goodput_note(category: str, rank, dt_s: float) -> None:
    """Donate ``dt_s`` wall seconds of ``category`` (productive | lost |
    snapshot | idle) for one rank.  Recorded even while probes are
    disabled (the zero_memory precedent): the goodput drill must stay
    assertable through a bench's bare arm, and the supervisor's round
    bookkeeping is never on a per-signal hot path."""
    if dt_s <= 0.0:
        return
    fam = _GOODPUT.get(category)
    if fam is None:
        raise ValueError(f"unknown goodput category: {category!r}")
    fam.labels(rank=str(rank)).inc(float(dt_s))
    total = sum(child.get() for f in _GOODPUT.values()
                for _, child in f.items())
    if total > 0.0:
        _GOODPUT_RATIO.set(
            sum(c.get() for _, c in _GOODPUT_PRODUCTIVE.items()) / total)


def goodput_totals() -> dict:
    """Per-category second sums across ranks — what the elastic drill
    reconciles against supervisor wall time."""
    return {cat: float(sum(child.get() for _, child in fam.items()))
            for cat, fam in _GOODPUT.items()}
