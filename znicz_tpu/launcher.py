"""Launcher — rebuild of veles/launcher.py :: Launcher.

Owns a workflow's lifecycle: device selection, optional snapshot resume,
initialize/run/stop, timing-table report.  The reference's
standalone/master/slave trichotomy collapses to SPMD (SURVEY.md §3.4): a
multi-host run is N identical processes that call
``jax.distributed.initialize`` (``multihost()``) and then run the same
standalone code path — XLA's collectives over ICI/DCN replace the ZeroMQ
job protocol, so there is no separate Server/Client pair to manage.
"""

from __future__ import annotations

import signal
import socket
import sys
from typing import Optional

from znicz_tpu.core.backends import AutoDevice, Device
from znicz_tpu.core.logger import Logger
from znicz_tpu.observe import probe
from znicz_tpu.resilience.retry import RetryPolicy
from znicz_tpu.snapshotter import restore_state

#: non-zero ranks wait for the coordinator under this schedule before
#: touching ``jax.distributed`` — bounded at ~60 s of backed-off TCP
#: probes.  Why a probe and not a retry around ``initialize`` itself:
#: this jaxlib's distributed client does NOT raise on a coordinator
#: timeout, it LOG(FATAL)s the whole process (absl ``client.h``), so
#: the only safe place to wait out a slow coordinator is before the
#: first ``initialize`` call.
DEFAULT_CONNECT_RETRY = dict(max_attempts=40, base_delay=0.1,
                             multiplier=1.4, max_delay=3.0,
                             retryable=(OSError,), seed=0)


class CoordinatorUnreachable(RuntimeError):
    """The multihost coordinator never accepted a connection within the
    bounded retry schedule."""


def wait_for_coordinator(coordinator: str,
                         policy: Optional[RetryPolicy] = None,
                         connect_timeout: float = 1.0) -> None:
    """Block until ``coordinator`` (``host:port``) accepts a TCP
    connection, retrying connect-refused / not-up under a bounded
    ``RetryPolicy``; exhaustion raises :class:`CoordinatorUnreachable`
    naming the address.  A bare TCP open+close is harmless to the gRPC
    coordination service behind the port."""
    policy = policy or RetryPolicy(**DEFAULT_CONNECT_RETRY)
    host, _, port = coordinator.rpartition(":")
    if not host or not port.isdigit():
        raise ValueError(f"coordinator address {coordinator!r} is not "
                         f"host:port")

    def probe() -> None:
        with socket.create_connection((host, int(port)),
                                      timeout=connect_timeout):
            pass

    try:
        policy.call(probe)
    except OSError as exc:
        raise CoordinatorUnreachable(
            f"multihost coordinator {coordinator} unreachable after "
            f"{policy.total_attempts} attempts "
            f"(last error: {exc!r}); is process 0 up?") from exc


def multihost(coordinator: str, num_processes: int, process_id: int,
              connect_policy: Optional[RetryPolicy] = None,
              initialization_timeout: Optional[int] = None) -> None:
    """Join a multi-host SPMD job (reference: the -l/-m master/slave flags;
    here every process is a peer).  Call before any jax device use.

    ``jax.distributed.initialize`` races a slow coordinator — and on
    loss it aborts the process instead of raising — so non-zero ranks
    first wait for the coordinator port under a bounded
    :class:`RetryPolicy` (``connect_policy``; see
    ``DEFAULT_CONNECT_RETRY``).  Rank 0 hosts the coordinator itself
    and skips the probe."""
    if process_id != 0:
        wait_for_coordinator(coordinator, connect_policy)
    import jax
    kwargs = {}
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = int(initialization_timeout)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)


class Launcher(Logger):
    """Boot/own one workflow run (reference: veles/launcher.py)."""

    def __init__(self, device: Optional[Device] = None,
                 snapshot: Optional[str] = None,
                 stealth: bool = False,
                 profile_dir: Optional[str] = None,
                 manhole_path: Optional[str] = None) -> None:
        super().__init__()
        self.device = device
        self.snapshot = snapshot
        #: stealth: suppress side services (plotters/web) — reference -s
        self.stealth = stealth
        #: when set, serve a live REPL into the running workflow on an
        #: AF_UNIX socket ("" = auto private path) — reference's manhole
        self.manhole_path = manhole_path
        self.manhole = None
        #: when set, the run is wrapped in ``jax.profiler.trace`` and the
        #: trace lands here (open with TensorBoard / xprof — SURVEY §6.1,
        #: the TPU-native upgrade over the reference's wall-clock table)
        self.profile_dir = profile_dir
        self.workflow = None
        self._interrupted = False
        self._terminated = False

    # -- the load/main pair handed to sample modules ------------------------
    def load(self, builder, **kwargs):
        """Reference ``load`` contract: build the workflow (module-supplied
        builder + kwargs), remember it, return (workflow, from_snapshot)."""
        with probe.setup_phase("load"):
            self.workflow = builder(**kwargs)
        return self.workflow, self.snapshot is not None

    def main(self, **_ignored):
        """Reference ``main`` contract: initialize, resume, run, stop."""
        if self.workflow is None:
            raise RuntimeError("load() was not called before main()")
        device = self.device if self.device is not None else AutoDevice()
        self.info(f"initializing {self.workflow.name} on {device!r}")
        with probe.setup_phase("initialize"):
            self.workflow.initialize(device=device)
        if self.snapshot:
            meta = restore_state(self.workflow, self.snapshot)
            self.info(f"resumed from {self.snapshot} "
                      f"(epoch {meta['loader']['epoch_number']})")
        if self.manhole_path is not None:
            # explicitly opt-in, so it is served even under --stealth
            # (stealth suppresses the *default* side services)
            from znicz_tpu.core.config import root
            from znicz_tpu.utils.manhole import Manhole
            self.manhole = Manhole(
                namespace={"wf": self.workflow, "launcher": self,
                           "root": root},
                path=self.manhole_path)
            self.manhole.start()
        prev = None
        prev_term = None
        profiling = False
        try:
            prev = signal.signal(signal.SIGINT, self._on_sigint)
            # elastic fleet teardown (ISSUE 9): SIGTERM = finish the
            # current epoch, publish a final snapshot, exit 143 — the
            # graceful half of kill-and-resume (SIGKILL is the other)
            prev_term = signal.signal(signal.SIGTERM, self._on_sigterm)
            if self.profile_dir:
                import jax
                jax.profiler.start_trace(self.profile_dir)
                profiling = True
            self.workflow.run()
        finally:
            if profiling:
                # a failing trace flush must not skip the rest of cleanup
                try:
                    import jax
                    jax.profiler.stop_trace()
                    self.info(f"profiler trace -> {self.profile_dir}")
                except Exception as exc:  # noqa: BLE001
                    self.warning(f"profiler trace failed: {exc!r}")
                else:
                    # the trace is on disk either way — a summary failure
                    # must not read as a broken trace
                    try:
                        from znicz_tpu.utils.profiling import (
                            format_summary, summarize_trace)
                        self.info("top ops by device time:\n" +
                                  format_summary(summarize_trace(
                                      self.profile_dir, top=15)))
                    except Exception as exc:  # noqa: BLE001
                        self.warning(
                            f"trace summary unavailable: {exc!r}")
            if self.manhole is not None:
                self.manhole.stop()
            if prev is not None:
                signal.signal(signal.SIGINT, prev)
            if prev_term is not None:
                signal.signal(signal.SIGTERM, prev_term)
            self.workflow.stop()
        self.info("timing:\n" + self.workflow.timing_table())
        if self._terminated:
            # snapshot-then-exit: the run stopped at an epoch boundary
            # (the same granularity as the snapshotter unit), so a final
            # export is a legitimate resume point; then exit with the
            # conventional 128+SIGTERM code so a fleet supervisor can
            # tell "terminated as asked" (143) from "completed" (0) —
            # a SIGTERM'd worker must NOT fall through to the workflow
            # module's post-run epilogue as if training had finished.
            # Only the elected writer exports: a non-zero rank's export
            # is a verify-poll, and when the fleet is tearing down
            # because rank 0 DIED that poll would burn the whole
            # SIGTERM grace waiting for a snapshot that never comes.
            from znicz_tpu.snapshotter import process_rank_world
            snapshotter = getattr(self.workflow, "snapshotter", None)
            if snapshotter is not None and \
                    process_rank_world()[0] == 0 and \
                    getattr(snapshotter, "target_workflow", None) is not None:
                try:
                    snapshotter.export()
                    self.info(f"SIGTERM: final snapshot -> "
                              f"{snapshotter.destination}")
                except Exception as exc:  # noqa: BLE001 — exit anyway
                    self.warning(f"SIGTERM: final snapshot failed: "
                                 f"{exc!r}")
            sys.exit(143)
        return self.workflow

    def _on_sigterm(self, signum, frame):
        # graceful half of the elastic fleet's kill path: finish the
        # epoch (the decision gate is checked at epoch boundaries, the
        # same granularity the snapshotter publishes at), then main()
        # exports a final snapshot and exits 143 instead of returning
        self._terminated = True
        self.warning("SIGTERM: finishing current epoch, then "
                     "snapshot-and-exit(143)")
        if self.workflow is not None and \
                getattr(self.workflow, "decision", None) is not None:
            self.workflow.decision.complete.set(True)

    def _on_sigint(self, signum, frame):
        # flip the decision's complete gate so the loop exits at the next
        # epoch boundary check; second ^C raises immediately
        if self._interrupted:
            raise KeyboardInterrupt
        self._interrupted = True
        self.warning("SIGINT: finishing current minibatch, then stopping "
                     "(press again to abort)")
        if self.workflow is not None and \
                getattr(self.workflow, "decision", None) is not None:
            self.workflow.decision.complete.set(True)
