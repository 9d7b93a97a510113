"""Failure detection + elastic recovery (checkpoint-based auto-resume).

The reference is thin here (SURVEY §5): in-process it surfaces async
errors at sync points (``threaded_engine.cc:474-487``), cross-process it
leans on ps-lite heartbeats and job-level restart by ``dmlc_tracker``;
there is no in-framework auto-resume.  This module fills the gap the
TPU-native way — on a TPU slice a failed host kills the whole SPMD job
and the recovery unit is *job restart from the newest checkpoint*:

* :class:`CheckpointManager` — atomic (write-temp + fsync + rename),
  versioned, checksummed, pruned checkpoints of params + optimizer/step
  state; ``save_async()`` snapshots to host memory synchronously and
  writes on a background thread (bounded to one in-flight write) so the
  training loop never blocks on the filesystem; ``latest()`` verifies
  per-file CRC32 checksums and *falls back* to the newest uncorrupted
  checkpoint, so a torn or bit-rotted write never strands the job.
* :class:`PreemptionHandler` — converts SIGTERM/SIGINT (the preemptible
  TPU-slice eviction notice) into a "checkpoint at the next step
  boundary, then exit with :data:`PREEMPTED_EXIT_CODE`" drain flow.
* :func:`supervise` — the job-level restarter (the ``dmlc_tracker``
  "restart dead jobs" analogue): reruns a training command until clean
  exit with exponential backoff + jitter between restarts, bounding
  restarts; a graceful preemption drain restarts WITHOUT charging the
  failure budget, and configurable exit codes (a deterministic assert)
  abort immediately instead of burning the budget.  Sets
  ``MXTPU_RESTART_COUNT`` so the script can tell a cold start from a
  resume.
* :class:`Watchdog` — liveness detection for hangs (a wedged collective
  never raises): if the training loop stops kicking it, the process is
  killed with a distinctive exit code so ``supervise`` restarts it.
  ``FusedTrainStep.__call__`` kicks the active watchdog automatically.
* :class:`FaultInjector` — deterministic fault injection for testing
  the recovery path (crash at step K on the first incarnation only).

Exact-resume contract: with deterministic data order and seeds, a run
that crashes and resumes must produce *bit-identical* final parameters
to an uninterrupted run — including mid-epoch crashes, provided the data
iterator's ``state_dict()`` (io.NDArrayIter / gluon DataLoader) rides
the checkpoint ``extra`` (tests/test_elastic.py asserts equality — the
same standard the dist_sync kvstore tests use).

See docs/FAULT_TOLERANCE.md for the commit protocol and env vars.
"""
from __future__ import annotations

import json
import os
import queue
import random as _pyrandom
import signal
import subprocess
import sys
import threading
import time
import zlib

import numpy as np

from .ndarray import utils as _nd_utils

__all__ = ["CheckpointManager", "FaultInjector", "InjectedFault",
           "PreemptionHandler", "PreemptionRequested", "Watchdog",
           "supervise", "active_watchdog", "install_preemption_drain",
           "WATCHDOG_EXIT_CODE", "PREEMPTED_EXIT_CODE",
           "NUMERIC_EXIT_CODE"]

WATCHDOG_EXIT_CODE = 75   # distinctive "stalled, please restart" status
PREEMPTED_EXIT_CODE = 76  # graceful drain: checkpointed, restart for free
NUMERIC_EXIT_CODE = 77    # sentinel escalation exhausted: params poisoned
#                           beyond local repair — restart from the newest
#                           verified checkpoint (retryable: supervise
#                           charges the normal failure budget)


def _log(msg):
    print("[elastic] %s" % msg, file=sys.stderr, flush=True)


def _crc32_file(path):
    """CRC32 of a file's bytes (streamed; the value recorded in the
    checkpoint meta)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def _fsync_path(path):
    with open(path, "rb") as f:
        os.fsync(f.fileno())


def _fsync_dir(path):
    """Durably record the renames themselves (POSIX: rename durability
    needs a directory fsync).  Best effort — not every FS allows it."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


class _SaveJob:
    def __init__(self, step, arrays, extra):
        self.step = step
        self.arrays = arrays
        self.extra = extra
        self.done = threading.Event()
        self.error = None

    def wait(self):
        self.done.wait()
        if self.error is not None:
            raise self.error


class CheckpointManager:
    """Versioned atomic checkpoints: ``prefix-####.params`` (the
    reference .params container format) + ``prefix-####.meta.json``
    (step counter, CRC32 checksums, user state such as optimizer
    hyper-state / epoch / iterator ``state_dict``).

    Atomicity: both files are written to ``.tmp`` paths, fsynced, and
    renamed; the meta file is renamed LAST and is the commit point, so a
    crash mid-save leaves the previous checkpoint as ``latest()``.

    Integrity: the meta records the params file's CRC32
    (``checksums["params"]``); ``latest()`` re-computes it and silently
    skips any step whose params are truncated/bit-flipped or whose meta
    is unreadable, returning the newest checkpoint that verifies.

    ``save_async()`` decouples the loop from the disk: the device→host
    snapshot happens synchronously (cheap d2h copy, consistent at the
    step boundary); the write+fsync+rename runs on a daemon thread with
    a bounded queue of ONE — a new ``save_async`` first waits for the
    in-flight write, so at most one checkpoint of host memory is pinned
    and writes can never pile up behind a slow disk.  Call :meth:`flush`
    before relying on the newest step being committed (it also re-raises
    any background write error).
    """

    def __init__(self, prefix, keep_n=3):
        self.prefix = prefix
        self.keep_n = keep_n
        d = os.path.dirname(os.path.abspath(prefix))
        os.makedirs(d, exist_ok=True)
        self._dir = d
        self._queue = None
        self._thread = None
        self._inflight = None

    def _params_path(self, step):
        return "%s-%04d.params" % (self.prefix, step)

    def _meta_path(self, step):
        return "%s-%04d.meta.json" % (self.prefix, step)

    # -- write path -------------------------------------------------------
    @staticmethod
    def _snapshot(params):
        """Device→host copy of a name->NDArray (or numpy) dict — the only
        part of an async save that must happen at the step boundary."""
        out = {}
        for k, v in dict(params).items():
            out[k] = v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)
        return out

    def _write(self, step, arrays, extra):
        """Write+fsync+rename one checkpoint (runs on the caller's thread
        for ``save`` and on the writer thread for ``save_async``)."""
        pp, mp = self._params_path(step), self._meta_path(step)
        _nd_utils.save(pp + ".tmp", arrays)
        _fsync_path(pp + ".tmp")
        crc = _crc32_file(pp + ".tmp")
        os.replace(pp + ".tmp", pp)
        # fault injection: die between the params rename and the meta
        # rename — the torn-save window the meta-last protocol exists for
        fi_step = int(os.environ.get("MXTPU_FI_CRASH_AFTER_PARAMS", "-1"))
        if (step == fi_step
                and int(os.environ.get("MXTPU_RESTART_COUNT", "0")) == 0):
            os._exit(23)
        with open(mp + ".tmp", "w") as f:
            json.dump({"step": int(step), "extra": extra or {},
                       "checksums": {"params": crc}}, f)
        _fsync_path(mp + ".tmp")
        os.replace(mp + ".tmp", mp)  # commit point
        _fsync_dir(self._dir)
        self._prune()

    def save(self, step, params, extra=None):
        """Synchronous checkpoint.  params: dict name -> NDArray (or
        numpy); extra: JSON-able dict.  Orders after any in-flight async
        write (so sync and async saves never interleave)."""
        self.flush()
        self._write(step, self._snapshot(params), extra)

    def save_async(self, step, params, extra=None):
        """Checkpoint without blocking the training loop on the disk.

        Synchronously snapshots ``params`` to host memory, waits for the
        previous async write (bounded queue of 1), then hands the write
        to the background thread.  Returns a handle with ``wait()``.
        Background errors surface on the next ``save_async``/``flush``.
        """
        arrays = self._snapshot(params)
        self.flush()  # bound: at most one write in flight
        if self._thread is None:
            self._queue = queue.Queue(maxsize=1)
            self._thread = threading.Thread(
                target=self._writer_loop, name="ckpt-writer", daemon=True)
            self._thread.start()
        job = _SaveJob(step, arrays, extra)
        self._inflight = job
        self._queue.put(job)
        return job

    def flush(self):
        """Wait for the in-flight async write; re-raise its error."""
        job, self._inflight = self._inflight, None
        if job is not None:
            job.wait()

    def _writer_loop(self):
        while True:
            job = self._queue.get()
            try:
                self._write(job.step, job.arrays, job.extra)
            except BaseException as e:  # surfaced by flush()
                job.error = e
            finally:
                job.done.set()

    # -- read path --------------------------------------------------------
    def steps(self):
        """Committed checkpoint steps, ascending (a meta file plus an
        existing params file; integrity is verified by ``latest()``)."""
        d = os.path.dirname(os.path.abspath(self.prefix)) or "."
        base = os.path.basename(self.prefix)
        out = []
        for fn in os.listdir(d):
            if fn.startswith(base + "-") and fn.endswith(".meta.json"):
                num = fn[len(base) + 1:-len(".meta.json")]
                if num.isdigit() and os.path.exists(
                        self._params_path(int(num))):
                    out.append(int(num))
        return sorted(out)

    def _verify_meta(self, step):
        """Parsed meta if the checkpoint passes integrity checks, else
        None (with a warning naming the failure)."""
        try:
            with open(self._meta_path(step)) as f:
                meta = json.load(f)
        except (OSError, ValueError) as e:
            _log("checkpoint step %d: unreadable meta (%s) — skipping"
                 % (step, e))
            return None
        want = (meta.get("checksums") or {}).get("params")
        if want is not None:
            try:
                got = _crc32_file(self._params_path(step))
            except OSError as e:
                _log("checkpoint step %d: unreadable params (%s) — "
                     "skipping" % (step, e))
                return None
            if got != want:
                _log("checkpoint step %d: params checksum mismatch "
                     "(recorded %08x, file %08x) — skipping"
                     % (step, want, got))
                return None
        return meta

    def latest(self):
        """(step, params, extra) of the newest *verified* checkpoint, or
        None on a cold start.

        Walks committed steps newest-first; a step with a truncated or
        bit-flipped params file (checksum mismatch), an invalid meta
        JSON, or an unloadable params container is skipped with a
        warning and the previous committed checkpoint wins."""
        for step in reversed(self.steps()):
            meta = self._verify_meta(step)
            if meta is None:
                continue
            try:
                params = _nd_utils.load(self._params_path(step))
            except Exception as e:  # pre-checksum checkpoints
                _log("checkpoint step %d: params failed to load (%s) — "
                     "skipping" % (step, e))
                continue
            return step, params, meta.get("extra", {})
        return None

    def _prune(self):
        for s in self.steps()[:-self.keep_n]:
            for p in (self._params_path(s), self._meta_path(s)):
                try:
                    os.remove(p)
                except OSError:
                    pass


class InjectedFault(RuntimeError):
    pass


class FaultInjector:
    """Deterministic crash for recovery tests: dies at step
    ``MXTPU_FI_AT_STEP`` but only on incarnation ``MXTPU_FI_AT_RESTART``
    (default 0 — the first run), so the supervised rerun survives.
    ``MXTPU_FI_AT_RESTART=-1`` faults on every incarnation (for testing
    restart-budget exhaustion)."""

    def __init__(self):
        self.at_step = int(os.environ.get("MXTPU_FI_AT_STEP", "-1"))
        self.at_restart = int(os.environ.get("MXTPU_FI_AT_RESTART", "0"))
        self.incarnation = int(os.environ.get("MXTPU_RESTART_COUNT", "0"))

    def maybe_fail(self, step):
        if step == self.at_step and self.at_restart in (-1,
                                                        self.incarnation):
            raise InjectedFault("injected fault at step %d (incarnation "
                                "%d)" % (step, self.incarnation))


class PreemptionRequested(RuntimeError):
    """Raised at a step boundary (FusedTrainStep / Trainer) after a
    drain signal arrived — unwind to the drain handler, checkpoint, and
    exit with :data:`PREEMPTED_EXIT_CODE`."""


class PreemptionHandler:
    """Graceful SIGTERM/SIGINT drain for preemptible slices.

    The first signal only sets a flag; the training loop observes it at
    the next step boundary (``requested`` / ``check()`` — FusedTrainStep
    and Trainer check automatically when handed a handler) and calls
    :meth:`drain` to write a final checkpoint and exit with
    :data:`PREEMPTED_EXIT_CODE`, which :func:`supervise` restarts
    without charging the failure budget.  A second signal while draining
    exits immediately (the eviction deadline is near; better to lose the
    tail than be SIGKILLed mid-write).
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT),
                 exit_code=PREEMPTED_EXIT_CODE):
        self._signals = tuple(signals)
        self.exit_code = exit_code
        self._requested = threading.Event()
        self._signal_count = 0
        self._prev = {}
        self._installed = False
        # registration happens during setup, before install() arms the
        # signal; Python delivers signals on the main thread, so the
        # iteration in _on_signal never overlaps add_callback
        self._callbacks = []  # mxlint: not-shared — registered pre-install, read on main thread

    def install(self):
        """Register the signal handlers (main thread only — CPython
        restriction).  Returns self for chaining."""
        for s in self._signals:
            self._prev[s] = signal.signal(s, self._on_signal)
        self._installed = True
        return self

    def uninstall(self):
        for s, prev in self._prev.items():
            signal.signal(s, prev)
        self._prev.clear()
        self._installed = False

    def add_callback(self, fn):
        """Register ``fn`` to run from the signal handler on the FIRST
        drain signal (e.g. ``ModelServer._drain_flag.set`` so admission
        closes immediately, before the step boundary).  ``fn`` runs in
        signal-handler context: it must be async-signal safe — an atomic
        flag/Event set, never lock acquisition or I/O."""
        self._callbacks.append(fn)
        return self

    def _on_signal(self, signum, frame):
        self._signal_count += 1
        if self._signal_count > 1:
            os._exit(self.exit_code)  # impatient second signal
        self._requested.set()
        for fn in self._callbacks:
            try:
                fn()
            except Exception:
                pass  # a broken callback must not mask the drain
        _log("received signal %d: draining at the next step boundary"
             % signum)

    @property
    def requested(self):
        return self._requested.is_set()

    def check(self):
        """Raise :class:`PreemptionRequested` if a drain was requested
        (call at step boundaries)."""
        if self._requested.is_set():
            raise PreemptionRequested(
                "preemption drain requested (signal received)")

    def drain(self, checkpoint_fn=None):
        """Write the final checkpoint (``checkpoint_fn``) and exit with
        the distinctive drain status."""
        if checkpoint_fn is not None:
            checkpoint_fn()
        _log("drain checkpoint written; exiting rc=%d" % self.exit_code)
        sys.exit(self.exit_code)


def install_preemption_drain(drain_flag_set, handler=None):
    """Wire a server's drain flag into SIGTERM/SIGINT (the rc-76
    graceful-drain contract, docs/FAULT_TOLERANCE.md).

    The one shared implementation behind
    ``ModelServer.install_preemption_drain`` /
    ``GenerationServer.install_preemption_drain`` and the fleet worker
    entrypoint: installs a fresh :class:`PreemptionHandler` when none is
    given (main thread only — CPython signal restriction) and registers
    ``drain_flag_set`` to run on the FIRST drain signal so admission
    closes immediately, before the step boundary.  ``drain_flag_set``
    runs in signal-handler context: it must be async-signal safe (an
    Event/flag set, never lock acquisition or I/O).  Returns the
    handler.
    """
    if handler is None:
        handler = PreemptionHandler().install()
    handler.add_callback(drain_flag_set)
    return handler


_active_watchdog = None


def active_watchdog():
    """The most recently started (and not stopped) Watchdog, or None.
    ``FusedTrainStep.__call__`` kicks it automatically."""
    return _active_watchdog


class Watchdog:
    """Hang detector: a daemon thread that calls ``on_stall`` (default:
    ``os._exit(WATCHDOG_EXIT_CODE)``) if ``kick()`` is not called within
    ``timeout`` seconds.  A wedged XLA collective hangs forever
    without raising — exiting with a distinctive status converts
    the hang into a restartable failure for :func:`supervise`.

    ``start()`` on an already-started watchdog raises (a silent double
    start would leave an orphan watcher holding a stale deadline);
    ``stop()`` joins the watcher thread so no timer survives it."""

    def __init__(self, timeout, on_stall=None):
        self.timeout = timeout
        self.on_stall = on_stall or (
            lambda: os._exit(WATCHDOG_EXIT_CODE))
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._started = False
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def start(self):
        global _active_watchdog
        if self._started:
            raise RuntimeError("Watchdog.start() called twice — one "
                               "watchdog, one watcher thread")
        self._started = True
        self._last = time.monotonic()
        self._thread.start()
        _active_watchdog = self
        return self

    def kick(self):
        self._last = time.monotonic()

    def stop(self):
        global _active_watchdog
        self._stop.set()
        if self._started and self._thread is not threading.current_thread():
            self._thread.join()
        if _active_watchdog is self:
            _active_watchdog = None

    def _watch(self):
        while not self._stop.wait(min(self.timeout / 4.0, 1.0)):
            if time.monotonic() - self._last > self.timeout:
                self.on_stall()
                return


def _backoff_delay(failures, base, cap=30.0):
    """Exponential backoff with jitter for restart ``failures`` (1-based):
    ``min(cap, base * 2**(failures-1))`` scaled by uniform [0.5, 1.0) —
    decorrelates a gang of workers restarting off the same fault."""
    if base <= 0:
        return 0.0
    return min(float(cap), float(base) * (2.0 ** (failures - 1))) \
        * (0.5 + 0.5 * _pyrandom.random())


def supervise(argv, max_restarts=3, env=None, logger=None, backoff=None,
              backoff_cap=30.0, nonretryable=None, max_preemptions=1000):
    """Run ``argv`` until clean exit, restarting on failure (job-level
    elasticity — the dmlc_tracker restart analogue, reference
    ``tools/launch.py`` job lifecycle).

    Each incarnation gets ``MXTPU_RESTART_COUNT`` in its env; the
    training script resumes from ``CheckpointManager.latest()``.

    * Failures restart after exponential backoff with jitter
      (``backoff`` base seconds, default ``MXTPU_RESTART_BACKOFF`` or
      1.0; capped at ``backoff_cap``).
    * rc == :data:`PREEMPTED_EXIT_CODE` (graceful drain) restarts
      immediately and does NOT count against ``max_restarts`` — a
      preempted worker did nothing wrong (bounded by
      ``max_preemptions`` as a runaway stop).
    * An rc in ``nonretryable`` (default: the comma list in
      ``MXTPU_NONRETRYABLE_EXIT_CODES``) raises immediately — a
      deterministic assertion failure must not burn the whole budget.

    Returns the number of restarts used.  Raises ``RuntimeError`` when
    the budget is exhausted or a non-retryable code is seen.
    """
    log = logger or (lambda msg: print("[supervise] %s" % msg,
                                       file=sys.stderr, flush=True))
    base_env = dict(env if env is not None else os.environ)
    if backoff is None:
        backoff = float(base_env.get(
            "MXTPU_RESTART_BACKOFF",
            os.environ.get("MXTPU_RESTART_BACKOFF", "1.0")))
    if nonretryable is None:
        raw = base_env.get(
            "MXTPU_NONRETRYABLE_EXIT_CODES",
            os.environ.get("MXTPU_NONRETRYABLE_EXIT_CODES", ""))
        nonretryable = {int(x) for x in raw.split(",") if x.strip()}
    nonretryable = frozenset(nonretryable)

    failures = 0
    preemptions = 0
    incarnation = 0
    while True:
        run_env = {**base_env, "MXTPU_RESTART_COUNT": str(incarnation)}
        r = subprocess.run(list(argv), env=run_env)
        rc = r.returncode
        if rc == 0:
            return incarnation
        if rc in nonretryable:
            raise RuntimeError(
                "job exited with non-retryable rc=%d (incarnation %d)"
                % (rc, incarnation))
        if rc == PREEMPTED_EXIT_CODE:
            preemptions += 1
            if preemptions > max_preemptions:
                raise RuntimeError(
                    "job preempted %d times — giving up" % preemptions)
            log("incarnation %d drained on preemption (rc=%d): "
                "restarting, failure budget untouched" % (incarnation, rc))
        else:
            failures += 1
            if failures > max_restarts:
                raise RuntimeError("job failed after %d restarts"
                                   % max_restarts)
            delay = _backoff_delay(failures, backoff, backoff_cap)
            log("incarnation %d exited rc=%d%s; restart %d/%d in %.2fs"
                % (incarnation, rc,
                   " (watchdog stall)" if rc == WATCHDOG_EXIT_CODE else "",
                   failures, max_restarts, delay))
            if delay:
                time.sleep(delay)
        incarnation += 1
