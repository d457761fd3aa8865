"""TaskRuntime: worker threads + pluggable scheduler + dependency system.

This is the paper's runtime assembled from its components:
  spawn()       -> pool-allocated Task, accesses registered through the
                   (wait-free | locked) dependency system
  worker loop   -> scheduler.get_ready_task (delegation / global-lock /
                   work-stealing), run, unregister -> successors become ready
  taskwait()    -> block until a task's body is done (generation-safe)
  task_group()  -> TaskGroup: await a whole spawn set + subtrees without
                   retaining any Task object
  barrier()     -> block until the runtime is quiescent

Ablation knobs mirror the paper's §6 variants:
  deps="waitfree"|"locked", scheduler="delegation"|"global-lock"|
  "work-stealing", use_pool=True|False.

Task lifecycle & ownership contract (spawn / retain / taskwait)
---------------------------------------------------------------
Every task carries a *completion token* count: one token for its body plus
one per live child (added at child spawn, dropped when the child fully
finishes). A task is *fully finished* only at token count zero — its whole
subtree is done — and only then is it counted out of the live set, handed to
its TaskGroup, retired (generation bump) and released to the pool. This
unifies what used to be two protocols (deferred unregister for locked deps,
immediate release for wait-free deps) and closes the lifetime hole where a
wait-free-mode parent could be recycled while its children still pointed at
it.

Who may hold a Task and for how long:

* ``spawn(...)`` returns the live ``Task``. The reference is guaranteed to
  denote that logical task only until the task's subtree completes; after
  that the pool may recycle the object. Holding it longer is *detected*, not
  undefined: every recycle bumps ``task.generation``.
* ``spawn(..., retain=True)`` opts the task out of pooling. The caller may
  keep the object indefinitely and read ``result`` / ``exception`` after
  completion. This is the required pattern for reading outputs.
* ``spawn(..., handle=True)`` returns a ``TaskRef`` stamped with the spawn
  generation *before* the task can run — the durable way to wait on a pooled
  task: ``taskwait(ref)`` returns immediately (True) if the logical task
  already finished and was recycled, instead of blocking on the recycled
  object's next occupant.
* ``taskwait(task_or_ref)`` waits for *body* completion. With a ``TaskRef``
  the spawn-time generation makes recycling fully detectable. With a bare
  ``Task`` the generation is captured at call time: recycling *during* the
  wait is detected (no orphaned-event hang), but recycling that happened
  *before* the call is indistinguishable from a fresh task — the wait then
  tracks the object's new occupant. Callers that may race completion must
  use ``handle=True`` (or ``retain=True``).
* ``task_group()`` returns a :class:`TaskGroup`; tasks spawned through it
  are accounted in the group, and ``group.wait()`` blocks until every one of
  them (including their nested subtrees, via completion tokens) fully
  finished — no Task references retained anywhere.

Errors: a failed task's exception is recorded (under a lock) and re-raised
by ``shutdown()`` / ``TaskGroup.wait()``. The error list is cleared on
raise, so a runtime (or group) is reusable after a failure; sibling errors
ride along on the raised exception's ``errors`` attribute.

Worker parking (per-worker slots; see repro.core.parking)
---------------------------------------------------------
Each worker owns a parking slot with the state machine RUNNING -> POLLING
-> PARKED. A worker that polls an empty scheduler a few times publishes
POLLING (``begin_poll``), re-polls once — the futex protocol that makes
lost wakeups impossible — and then blocks on its *own* condition.
``add_ready_task`` (via a wake hook every scheduler calls after the task is
visible) wakes exactly ONE parked worker, preferring the task's NUMA node
and scanning from a round-robin start; a worker that dequeues work while
others are parked and the scheduler still has pending tasks chains one more
wake. The park timeout adapts to an EWMA of observed task inter-arrival —
bursty fine-grained phases re-poll within ~1 ms while idle phases back off
exponentially to a long sleep — so even a pathological missed wake costs a
bounded, load-proportional delay. ``TaskRuntime(parking="eventcount")``
selects the previous single-condition design (kept for the wake-latency
ablation).

Worksharing tasks (taskloop)
----------------------------
``taskloop(n_or_range, body, chunk=..., ...)`` executes a data-parallel
loop as ONE pooled descriptor (``WorksharingTask``) instead of one task per
iteration — the "worksharing tasks" primitive (Maroñas et al.). Loop-level
dependencies are registered once through the ordinary dependency system;
when the descriptor becomes ready it is posted on a *worksharing board*
shared by every scheduler policy, the wake fan-out is sized to the number
of claimable chunks, and idle workers whose queues are empty join the live
loop and claim chunks off an atomic cursor. The LAST participant out
merges per-participant reduction partials (``reduce=``/``reduce_init=``)
and runs the normal completion path, so TaskGroup / taskwait / barrier /
cancellation semantics are unchanged; group cancellation stops un-claimed
chunks at the cursor. See docs/RUNTIME.md, "Worksharing tasks".

Cancellation (TaskGroup.cancel)
-------------------------------
``group.cancel()`` is cooperative and epoch-based: every task spawned into
a group is stamped with the group's cancel epoch; ``cancel()`` bumps the
epoch, so (1) new spawns into the group are refused (``spawn`` returns
``None``), and (2) still-queued member tasks are *dropped at dequeue* — the
worker skips the body but runs the full completion path (dependency
unregister, completion tokens, group accounting, pool release), so
successors, ``taskwait`` and pooled-task recycling all behave exactly as if
the body had run and returned None. Tasks already running are never
interrupted. A group created with ``cancel_on_error=True`` cancels itself
when the first member task fails — the serve engine uses this to stop its
decode chain on the first error and for ``stop(drain=False)``.
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Callable, Iterable, Optional, Union

from repro.core.asm import MailBox, MailBoxPool, WaitFreeDependencySystem
from repro.core.atomic import AtomicU64
from repro.core.deps_locked import LockedDependencySystem
from repro.core.instrument import CounterPlane, Tracer
from repro.core.parking import PARKING_KINDS
from repro.core.pool import TaskPool
from repro.core.scheduler import SwitchableScheduler, WorksharingBoard
from repro.core.task import DONE, Task, TaskRef, _NO_PARTIAL

_current_task = threading.local()

# worker parking knobs: how many empty polls before parking, and the timed
# backstop so a (theoretically possible) lost wakeup is a bounded delay
_PARK_AFTER_SPINS = 20
_PARK_TIMEOUT_S = 0.05          # fixed timeout (eventcount mode, wait slices)
_PARK_TIMEOUT_MIN_S = 0.001     # adaptive floor: burst-phase re-poll period
_PARK_TIMEOUT_MAX_S = 0.25      # adaptive ceiling: idle-phase sleep
_PARK_EWMA_ALPHA = 0.1          # inter-arrival EWMA smoothing
_PARK_EWMA_MULT = 32.0          # timeout = MULT * EWMA(inter-arrival)


def current_task() -> Optional[Task]:
    return getattr(_current_task, "t", None)


# taskloop reduce= resolution: named ops with identities, or a callable
# with an explicit initial value
_REDUCE_OPS = {
    "+": lambda a, b: a + b,
    "*": lambda a, b: a * b,
    "max": max,
    "min": min,
}
_REDUCE_IDENTITY = {"+": 0, "*": 1}


def _resolve_reduce(reduce, reduce_init):
    if callable(reduce):
        if reduce_init is None:
            raise ValueError("taskloop: callable reduce= needs reduce_init=")
        return reduce, reduce_init
    fn = _REDUCE_OPS.get(reduce)
    if fn is None:
        raise ValueError(f"taskloop: unknown reduce op {reduce!r} "
                         "(use '+', '*', 'max', 'min' or a callable)")
    if reduce_init is None:
        reduce_init = _REDUCE_IDENTITY.get(reduce)
        if reduce_init is None:
            raise ValueError(f"taskloop: reduce={reduce!r} has no identity; "
                             "pass reduce_init=")
    return fn, reduce_init


class TaskGroup:
    """Await a set of tasks (and their subtrees) without retaining them.

    Producer-side accounting is two atomic counters — no locks on the spawn
    or completion fast path; ``wait`` blocks on an event armed exactly when
    the outstanding count leaves / reaches zero.

    ``cancel()`` stops admitting spawns and drops still-queued member tasks
    at dequeue (see the module docstring's cancellation contract). With
    ``cancel_on_error=True`` the group cancels itself when the first member
    task fails.
    """

    def __init__(self, runtime: "TaskRuntime", name: str = "",
                 cancel_on_error: bool = False):
        self._rt = runtime
        self.name = name
        self.cancel_on_error = cancel_on_error
        self._outstanding = AtomicU64(0)
        self._spawned = AtomicU64(0)
        self._idle = threading.Event()
        self._idle.set()
        # serializes the event arm/disarm against the count it reflects:
        # taken only on 0<->1 boundary transitions, never on the steady path
        self._event_lock = threading.Lock()
        self._errors: list[BaseException] = []
        self._errors_lock = threading.Lock()
        # cancel token: tasks are stamped with the epoch at spawn; cancel()
        # bumps it, so queued members are dropped at dequeue by epoch
        # mismatch (generation-checked: a recycled pooled Task re-stamps)
        self._cancel_epoch = AtomicU64(0)
        self._cancel_once = AtomicU64(0)
        self._cancelled = False
        # invoked exactly once, after the epoch bump, whoever triggers the
        # cancel (explicit cancel() or the first error under
        # cancel_on_error) — e.g. the serve engine releases its request
        # waiters here. A raising callback is recorded as a group error,
        # never propagated into the cancelling worker's loop.
        self.on_cancel: Optional[Callable[[], None]] = None

    # -- spawn-side ----------------------------------------------------
    def spawn(self, fn: Callable, args: tuple = (), kwargs=None,
              **kw) -> Union[Task, TaskRef, None]:
        """Spawn into this group; returns None once the group is cancelled
        (admission refused) — see TaskRuntime.spawn for the other kinds."""
        return self._rt.spawn(fn, args, kwargs, group=self, **kw)

    def _attach(self, task: Task):
        self._spawned.fetch_add(1)
        if self._outstanding.fetch_add(1) == 0:
            with self._event_lock:  # re-check: a racing done may have set()
                if self._outstanding.load() > 0:
                    self._idle.clear()

    # -- cancellation --------------------------------------------------
    @property
    def cancelled(self) -> bool:
        return self._cancelled

    def cancel(self):
        """Stop admitting spawns into this group and drop its still-queued
        tasks at dequeue. Running tasks finish normally; ``wait`` then
        returns once the survivors completed. Idempotent — concurrent
        cancels collapse to one epoch bump and one on_cancel call."""
        if self._cancelled:  # racy fast path; the CAS below decides
            return
        if not self._cancel_once.compare_exchange(0, 1):
            return
        self._cancelled = True
        san = self._rt.san
        if san is not None:
            # record the canceller's clock BEFORE the epoch bump publishes
            # the cancel: every member skipped at dequeue joins it
            san.on_group_cancel(self)
        self._cancel_epoch.fetch_add(1)
        self._rt.tracer.event("group.cancel", self._outstanding.load())
        cb = self.on_cancel
        if cb is not None:
            try:
                cb()
            except BaseException as e:  # surfaced by wait(), not the worker
                with self._errors_lock:
                    self._errors.append(e)

    # -- completion-side (called by the runtime at full finish) --------
    def _task_done(self, task: Task):
        if task.exception is not None:
            with self._errors_lock:
                self._errors.append(task.exception)
            if self.cancel_on_error:
                self.cancel()
        if self._outstanding.fetch_add(-1) == 1:
            with self._event_lock:  # re-check: a racing spawn re-armed
                if self._outstanding.load() == 0:
                    self._idle.set()

    # -- consumer ------------------------------------------------------
    @property
    def pending(self) -> int:
        return self._outstanding.load()

    def wait(self, timeout: Optional[float] = None,
             raise_errors: bool = True) -> bool:
        """Block until every task spawned through this group fully finished
        (subtrees included). Returns False on timeout. Re-raises the first
        collected task error (clearing the list) when raise_errors is set."""
        exp = self._rt._explorer
        if exp is not None:
            st = exp.wait_until(
                lambda: self._outstanding.load() == 0, kind="group-wait",
                label=f"group.wait({self.name or 'anon'})", group=self,
                task=current_task(), timed=timeout is not None)
            if st != "disabled":
                if self._outstanding.load() != 0:
                    return False
                if raise_errors:
                    self.raise_errors()
                self._san_joined()
                return True
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            budget = None if deadline is None else deadline - time.monotonic()
            if budget is not None and budget <= 0:
                if self._outstanding.load() != 0:
                    return False
                if raise_errors:
                    self.raise_errors()
                self._san_joined()
                return True
            if not self._idle.wait(budget):
                return False
            if self._outstanding.load() == 0:
                if raise_errors:
                    self.raise_errors()
                self._san_joined()
                return True
            # the event was re-armed by a concurrent spawn between set() and
            # clear(); yield and re-wait on the (soon cleared) event
            time.sleep(0)

    def _san_joined(self):
        """Successful wait: every finished member happens-before the waiter."""
        san = self._rt.san
        if san is not None:
            san.on_group_wait(self)

    def raise_errors(self):
        with self._errors_lock:
            errs, self._errors = self._errors, []
        if errs:
            raise _attach_siblings(errs)

    @property
    def errors(self) -> tuple:
        with self._errors_lock:
            return tuple(self._errors)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.wait(raise_errors=exc_type is None)

    def __repr__(self):
        return (f"TaskGroup({self.name!r}, pending={self.pending}, "
                f"spawned={self._spawned.load()})")


def _attach_siblings(errs: list) -> BaseException:
    """Primary error carries the rest: ``errors`` attribute + __context__."""
    primary = errs[0]
    try:
        primary.errors = tuple(errs)
        if len(errs) > 1 and errs[1] is not primary \
                and primary.__context__ is None:
            primary.__context__ = errs[1]
    except Exception:
        pass  # exceptions with __slots__ / frozen attrs: best effort
    return primary


class _MailboxLease:
    """Thread-local holder for a pooled MailBox. The finalizer returns the
    box to the pool when the owning thread's locals are collected — NOT a
    __del__ on MailBox itself, because the pool's free list must be able to
    hold strong references to recycled boxes."""

    __slots__ = ("mb", "_fin", "__weakref__")

    def __init__(self, pool):
        self.mb = pool.acquire()
        self._fin = weakref.finalize(self, pool.release, self.mb)


class TaskRuntime:
    def __init__(self, n_workers: int = 4, *, scheduler: str = "delegation",
                 deps: str = "waitfree", use_pool: bool = True,
                 policy: str = "fifo", n_numa: int = 1,
                 tracer: Optional[Tracer] = None,
                 spsc_capacity: int = 256, parking: str = "slots",
                 sanitize: Union[bool, str, None] = None,
                 explore=None, name: str = "", tune=False):
        self.n_workers = n_workers
        # name distinguishes runtimes sharing one process (RuntimeCluster):
        # it prefixes worker thread names and, critically, the schedule
        # explorer's thread ids — two anonymous runtimes would both register
        # workers as "w0" and the second would shadow the first's wait state
        self.name = name
        self.tracer = tracer or Tracer(enabled=False)
        self.pool = TaskPool(enabled=use_pool)
        if deps == "waitfree":
            self.deps = WaitFreeDependencySystem()
            self._defer_unregister = False
        elif deps == "locked":
            self.deps = LockedDependencySystem()
            self._defer_unregister = True  # conservative nesting semantics
        else:
            raise ValueError(deps)
        # counter plane (core/instrument.py): per-worker single-writer
        # counters the hot paths bump and the tune controller samples
        self.counters = CounterPlane(n_workers)
        # stable facade: the concrete policy impl behind it can be
        # hot-swapped at runtime (retune / repro.core.tune). Validates
        # scheduler and policy names up front with a clear ValueError.
        self.scheduler = SwitchableScheduler(
            scheduler, n_workers, policy=policy, n_numa=n_numa,
            spsc_capacity=spsc_capacity, instrument=self.tracer,
            counters=self.counters)
        # wake hook: every scheduler calls this once the task is visible to
        # consumers, so the single-wake decision sits next to the enqueue
        self.scheduler.on_enqueue = self._on_enqueue
        # worksharing: live taskloop descriptors live on one board shared
        # by every scheduler policy; idle workers claim chunks off it
        self.ws_board = WorksharingBoard()
        self.scheduler.set_ws_board(self.ws_board)

        self._live = AtomicU64(0)  # created-but-not-fully-finished tasks
        self._quiescent = threading.Event()
        self._quiescent.set()
        # serializes quiescent arm/disarm against the count it reflects:
        # taken only on 0<->1 boundary transitions (same pattern as
        # TaskGroup._event_lock) so a spawn racing the last finalize cannot
        # leave the event set while a task is live
        self._quiescent_lock = threading.Lock()
        self._stop = False
        self._threads: list[threading.Thread] = []
        self._started = False
        self._mailboxes = threading.local()
        self._mb_pool = MailBoxPool(self._on_access_ready)
        self._errors: list[BaseException] = []
        self._errors_lock = threading.Lock()
        # worker parking: per-worker slots (default) or the PR-1 global
        # eventcount ablation; see repro.core.parking
        self.parking_kind = parking
        self._n_numa = max(1, n_numa)
        self._parking = PARKING_KINDS[parking](n_workers, n_numa=n_numa)
        # adaptive park timeout: EWMA of task inter-arrival (advisory —
        # plain, racy updates; every consumer clamps to [MIN, MAX])
        self._ewma_arrival_s = 0.005
        self._last_arrival_ns = 0
        # park-timeout knobs, per runtime (defaults = the historical module
        # constants). The tune controller adjusts these at runtime; reads
        # are racy-but-clamped, so a mid-flight change is only advisory.
        self.park_timeout_min_s = _PARK_TIMEOUT_MIN_S
        self.park_timeout_max_s = _PARK_TIMEOUT_MAX_S
        self.park_ewma_alpha = _PARK_EWMA_ALPHA
        self.park_ewma_mult = _PARK_EWMA_MULT
        # wake fan-out: parked workers woken per enqueue. 1 (the futex
        # single-wake default) unless the controller widens it to absorb
        # bursts; clamped to n_workers at the wake site.
        self.wake_fanout = 1
        # tasksan (repro.analyze.tsan): sanitize=True raises TaskSanError at
        # shutdown, "report" only collects; None defers to REPRO_SANITIZE
        # ("1" -> True, "report" -> report mode). Off (None on every hook
        # site) costs one attribute check per hook. Passing an existing
        # TaskSanitizer instance shares it across runtimes (RuntimeCluster)
        # so cross-runtime handoffs are checked in one clock domain; the
        # owner of a shared instance flushes/checks it, not shutdown().
        if sanitize is None:
            env = os.environ.get("REPRO_SANITIZE", "")
            sanitize = "report" if env == "report" \
                else env not in ("", "0", "false")
        self.san = None
        self._san_owned = True
        if sanitize:
            from repro.analyze.tsan import TaskSanitizer
            if isinstance(sanitize, TaskSanitizer):
                self.san = sanitize
                self._san_owned = False
            else:
                self.san = TaskSanitizer(
                    raise_on_shutdown=(sanitize != "report"))
            self.san.install(self)
        # taskcheck (repro.analyze.explore): explore=<ScheduleExplorer|
        # SchedulePolicy|True> serializes every runtime thread behind the
        # explorer's token and systematically explores interleavings. Off
        # (None on every hook site) costs one attribute check per site,
        # and the lock hooks only exist inside contended wait loops.
        self._explorer = None
        if explore is not None and explore is not False:
            from repro.analyze.explore import (ScheduleExplorer,
                                               SchedulePolicy)
            if isinstance(explore, ScheduleExplorer):
                self._explorer = explore
            elif isinstance(explore, SchedulePolicy):
                self._explorer = ScheduleExplorer(explore)
            else:  # explore=True: default preemption-bounded policy
                self._explorer = ScheduleExplorer()
            self._explorer.install(self)
        # self-tuning controller (repro.core.tune): tune=True samples the
        # counter plane on a background thread and retunes the runtime when
        # it detects a pathology. tune= also accepts a TuneConfig (or a
        # kwargs dict for one). Never started under a schedule explorer —
        # exploration owns the schedule; tests drive retune() directly.
        self.tuner = None
        if tune:
            from repro.core.tune import TuneConfig, TuneController
            if isinstance(tune, TuneConfig):
                cfg = tune
            elif isinstance(tune, dict):
                cfg = TuneConfig(**tune)
            else:
                cfg = TuneConfig()
            self.tuner = TuneController(self, cfg)

    # ---------------------------------------------------------------- infra
    @property
    def scheduler_kind(self) -> str:
        """The currently-installed scheduler implementation's kind (tracks
        hot-swaps; was a plain attribute before the runtime became
        retunable)."""
        return self.scheduler.kind

    @property
    def scheduler_policy(self) -> str:
        return self.scheduler.policy

    def retune(self, *, scheduler: Optional[str] = None,
               policy: Optional[str] = None,
               park_timeout_min_s: Optional[float] = None,
               park_timeout_max_s: Optional[float] = None,
               park_ewma_alpha: Optional[float] = None,
               park_ewma_mult: Optional[float] = None,
               wake_fanout: Optional[int] = None) -> Optional[int]:
        """Adjust the runtime while it runs. Safe from any thread.

        ``scheduler``/``policy`` hot-swap the scheduler implementation via
        the drain-and-switch protocol (see SwitchableScheduler); the park
        knobs and ``wake_fanout`` are plain advisory stores (readers clamp,
        so a racy read at worst perturbs one timeout). Returns the number
        of queued tasks moved by a scheduler switch, or None if no switch
        happened. Unknown names raise ValueError before anything changes.
        """
        from repro.core.tune import KNOB_IDS
        moved = None
        if scheduler is not None or policy is not None:
            moved = self.scheduler.switch(scheduler, policy)
            if moved >= 0:
                self.tracer.event("tune.switch", moved)
        for knob, value in (("park_timeout_min_s", park_timeout_min_s),
                            ("park_timeout_max_s", park_timeout_max_s),
                            ("park_ewma_alpha", park_ewma_alpha),
                            ("park_ewma_mult", park_ewma_mult),
                            ("wake_fanout", wake_fanout)):
            if value is None:
                continue
            setattr(self, knob, value)
            self.tracer.event("tune.knob", KNOB_IDS[knob])
        return moved

    def _mailbox(self) -> MailBox:
        """Thread-local MailBox, leased from a shared pool: worker threads
        reuse one box across every task they run, and a box leased by a
        transient producer thread returns to the pool when the thread dies
        (weakref.finalize on the lease), carrying its recycled message
        objects to the next lineage instead of being rebuilt per thread."""
        lease = getattr(self._mailboxes, "lease", None)
        if lease is None:
            lease = _MailboxLease(self._mb_pool)
            lease.mb.san = self.san  # boxes circulate within one runtime
            lease.mb.exp = self._explorer
            self._mailboxes.lease = lease
        return lease.mb

    def _on_access_ready(self, access):
        access.task.access_satisfied(access)

    def start(self):
        if self._started:
            return self
        self._started = True
        self._stop = False
        exp = self._explorer
        if exp is not None:
            # the caller becomes "main" in the serialized world; it takes
            # the token first, so workers block until it yields
            exp.register("main")
        prefix = f"repro-{self.name}-worker" if self.name else "repro-worker"
        for wid in range(self.n_workers):
            t = threading.Thread(target=self._worker, args=(wid,),
                                 name=f"{prefix}-{wid}", daemon=True)
            t.start()
            self._threads.append(t)
        if exp is not None:
            exp.await_threads([self._worker_id(w)
                               for w in range(self.n_workers)])
        if self.tuner is not None and exp is None:
            # never under an explorer: the controller thread would act
            # outside the serialized world (explored tests call retune()
            # directly from registered threads instead)
            self.tuner.start()
        return self

    def _worker_id(self, wid: int) -> str:
        """Explorer thread id for worker ``wid`` (name-prefixed so runtimes
        sharing one explorer don't shadow each other's registrations)."""
        return f"{self.name}:w{wid}" if self.name else f"w{wid}"

    def shutdown(self, wait: bool = True):
        if self.tuner is not None:
            self.tuner.stop()  # no retunes during drain/teardown
        if wait:
            self.barrier()
        self._stop = True
        exp = self._explorer
        if exp is not None:
            # end of the schedule: stop serializing so workers can observe
            # _stop and exit natively
            exp.release_all()
        self._parking.wake_all()
        for t in self._threads:
            t.join(timeout=5)
        self._threads.clear()
        self._started = False
        if self._quiescent.is_set():
            self.collect()
        san = self.san
        if san is not None and self._san_owned:
            san.flush_report()  # CI artifact (REPRO_SANITIZE_REPORT)
        with self._errors_lock:
            errs, self._errors = self._errors, []
        if errs:
            raise _attach_siblings(errs)
        if san is not None and self._san_owned and san.raise_on_shutdown:
            san.check()

    def collect(self) -> int:
        """Prune dependency-system lineage bookkeeping. Safe only while the
        runtime is quiescent AND the caller guarantees no spawn is in flight
        (single-creator programs between phases). No-op otherwise."""
        if not self._quiescent.is_set():
            return 0
        san = self.san
        if san is not None:
            # quiescence at collect() is a full happens-before barrier:
            # retire the pre-collect shadow state so lineage reuse after
            # collection is not reported against it
            san.on_collect()
        return self.deps.collect()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(wait=exc[0] is None)

    # ---------------------------------------------------------------- spawn
    def spawn(self, fn: Callable, args: tuple = (), kwargs=None, *,
              name: str = "", reads: Iterable = (), writes: Iterable = (),
              rw: Iterable = (), reductions: Iterable = (),
              commutative: Iterable = (), affinity: Optional[int] = None,
              parent: Optional[Task] = None, retain: bool = False,
              group: Optional[TaskGroup] = None, detached: bool = False,
              handle: bool = False) -> Union[Task, TaskRef, None]:
        # cancelled group: refuse admission. The epoch is read BEFORE the
        # admission check so a cancel() racing this spawn either rejects it
        # here or (epoch already bumped past the stamp) drops it at dequeue
        # — after cancel() returns, no newly spawned member body can run.
        if group is not None:
            cancel_epoch = group._cancel_epoch.load()
            if group._cancelled:
                self.tracer.event("task.cancel", 0)
                return None
        # detached=True spawns a root task even from inside a running task:
        # self-perpetuating loops (e.g. the serve decode chain) must NOT
        # parent each iteration on the previous one, or completion tokens
        # keep the whole chain alive and no task is ever recycled
        if parent is None and not detached:
            parent = current_task()
        task = self.pool.acquire()
        task.init(fn, args, kwargs, name=name, parent=parent, reads=reads,
                  writes=writes, rw=rw, reductions=reductions,
                  commutative=commutative, affinity=affinity)
        if retain:
            task.pooled = False  # caller reads .result after completion
        task.group = group
        if group is not None:
            task._cancel_epoch = cancel_epoch
        task.on_ready = self._task_ready
        task.created_ns = time.monotonic_ns()
        ref = self._publish_task(task, group, parent, handle)
        return ref if handle else task

    def _publish_task(self, task: Task, group: Optional[TaskGroup],
                      parent: Optional[Task],
                      make_ref: bool) -> Optional[TaskRef]:
        """Shared spawn/taskloop publication tail. The ref must be stamped
        before the task is published to the dependency system: once
        registered it may run, finish and be recycled before the spawning
        call even returns."""
        ref = TaskRef(task) if make_ref else None
        if parent is not None:
            parent._completion.fetch_add(1)  # spawner's body token is held
        if group is not None:
            group._attach(task)
        if self._live.fetch_add(1) == 0:
            with self._quiescent_lock:  # re-check: a racing finalize set()
                if self._live.load() > 0:
                    self._quiescent.clear()
        self.tracer.event("task.create", task.task_id)
        self.counters.w(getattr(_current_task, "wid", None)).created += 1
        san = self.san
        if san is not None:
            # before registration: once published the task may run, finish
            # and be recycled on another worker before spawn returns
            san.on_spawn(task, task.parent)
        self.deps.register_task(task, self._mailbox())
        return ref

    def taskloop(self, iterations, body: Callable, *, chunk=None,
                 name: str = "", reads: Iterable = (), writes: Iterable = (),
                 rw: Iterable = (), reductions: Iterable = (),
                 commutative: Iterable = (), affinity: Optional[int] = None,
                 parent: Optional[Task] = None, retain: bool = False,
                 group: Optional[TaskGroup] = None, detached: bool = False,
                 handle: bool = False, wait: bool = False,
                 reduce=None, reduce_init=None):
        """Execute a data-parallel loop as ONE worksharing task.

        ``iterations`` is an int ``n`` (iterates ``[0, n)``) or a step-1
        ``range``. ``body(lo, hi)`` is called once per claimed chunk with a
        half-open sub-range; with ``reduce=`` set it is ``body(lo, hi, acc)
        -> acc`` threading a per-participant private accumulator, and the
        partials are merged ONCE by the last participant (``reduce`` is
        ``'+'``/``'*'``/``'max'``/``'min'`` or a callable ``(a, b) -> a⊕b``
        with an explicit ``reduce_init``).

        ``chunk`` is the iterations-per-claim grain (``None``/``'auto'``
        picks ~4 chunks per worker). Dependencies (``reads``/``writes``/
        ``rw``/``reductions``/``commutative``) are LOOP-level: registered
        once for the whole range through the ordinary dependency system.

        Returns like ``spawn`` (Task / TaskRef with ``handle=True`` / None
        when the group is cancelled) — except ``wait=True``, where the
        caller participates in its own loop, blocks until the descriptor
        fully finished, and gets the merged reduction result (or None).
        """
        if isinstance(iterations, range):
            if iterations.step != 1:
                raise ValueError("taskloop supports step-1 ranges only "
                                 "(map other strides inside the body)")
            start, stop = iterations.start, iterations.stop
        else:
            start, stop = 0, int(iterations)
        n = max(0, stop - start)
        if chunk is None or chunk == "auto":
            # ~4 chunks per worker: enough slack that a straggler worker
            # can be back-filled, few enough that claim overhead is noise
            chunk = max(1, -(-n // (4 * max(1, self.n_workers))))
        chunk = max(1, int(chunk))
        if reduce is not None:
            reduce, reduce_init = _resolve_reduce(reduce, reduce_init)
        # group admission: same epoch-read-before-check contract as spawn
        if group is not None:
            cancel_epoch = group._cancel_epoch.load()
            if group._cancelled:
                self.tracer.event("task.cancel", 0)
                return None
        if parent is None and not detached:
            parent = current_task()
        task = self.pool.acquire_ws()
        task.init(body, name=name or getattr(body, "__name__", "taskloop"),
                  parent=parent, reads=reads, writes=writes, rw=rw,
                  reductions=reductions, commutative=commutative,
                  affinity=affinity)
        task.init_loop(start, stop, chunk, body,
                       reduce=reduce, reduce_init=reduce_init)
        if retain:
            task.pooled = False  # caller reads .result after completion
        task.group = group
        if group is not None:
            task._cancel_epoch = cancel_epoch
        task.on_ready = self._task_ready
        task.created_ns = time.monotonic_ns()
        box = None
        if wait:
            # one-slot box the finalizer fills: the merged result stays
            # readable after the pooled descriptor is recycled
            box = task._ws_result_box = []
        ref = self._publish_task(task, group, parent, handle or wait)
        if wait:
            self._taskloop_wait(task, ref)
            return box[0] if box else None
        return ref if handle else task

    def _taskloop_wait(self, ws, ref: TaskRef) -> None:
        """``wait=True``: the caller participates in its own loop (claims
        chunks exactly like a worker) and then blocks until the descriptor
        — including chunks claimed by other participants — finished. A join
        that lands on the pool object's NEXT occupant (recycle race) just
        helps that loop; ``ref.done`` is already True then."""
        while not ref.done:
            if ws.ws_join():
                self._ws_participate(ws, getattr(_current_task, "wid", None))
                break
            # not yet open (loop dependencies pending) or already closing:
            # timed waits keep this responsive either way
            self.taskwait(ref, timeout=0.002)
        self.taskwait(ref)

    def task_group(self, name: str = "",
                   cancel_on_error: bool = False) -> TaskGroup:
        return TaskGroup(self, name, cancel_on_error=cancel_on_error)

    def _task_ready(self, task: Task):
        task.ready_ns = time.monotonic_ns()
        exp = self._explorer
        if exp is not None:
            # enqueue is a decision point: the explorer may run a consumer
            # (or another producer) before this task becomes visible
            exp.yield_point("task.ready")
        san = self.san
        if san is not None:
            # locked-deps release joins must land before a worker can pick
            # the task up (it becomes runnable at add_ready_task below)
            san.on_task_ready(task)
        self.tracer.event("task.ready", task.task_id)
        self._observe_arrival(task.ready_ns)
        if task.is_worksharing:
            self._worksharing_ready(task)
            return
        # both hints always travel: the facade's current implementation
        # decides which it uses (NUMA buffer for delegation, owning deque
        # for work-stealing), so a hot-swap never changes this call site
        self.scheduler.add_ready_task(
            task, numa_hint=task.affinity or 0,
            worker_id=getattr(_current_task, "wid", None))
        # the wake happens via the scheduler's on_enqueue hook

    def _worksharing_ready(self, ws) -> None:
        """A worksharing descriptor became READY: open it, post it on the
        board (never into the task queues — every policy polls the board
        on queue miss), and size the wake fan-out to the number of
        claimable chunks instead of the usual single wake."""
        ws.ws_publish()
        if ws.ws_nchunks == 0:
            # empty range: nothing to claim — complete the descriptor
            # inline through the normal participation/finalize path
            self._run_worksharing(ws, getattr(_current_task, "wid", None))
            return
        self.ws_board.post(ws)
        self.tracer.event("sched.add", ws.task_id)
        n = min(ws.ws_remaining() or 1, self.n_workers)
        prefer_numa = ws.affinity if self._n_numa > 1 else None
        woken = self._parking.wake_many(n, prefer_numa=prefer_numa)
        if woken:
            self.tracer.event("worker.wake", woken)
        san = self.san
        if san is not None:
            san.on_enqueue_outcome(woken > 0, self._parking.n_idle,
                                   self.scheduler.pending(), origin=self)

    # ---------------------------------------------------------------- work
    def _drop_token(self, task: Task):
        """Drop one completion token; at zero the task is fully finished.
        Iterative (not recursive) so deep nesting chains cannot overflow."""
        t: Optional[Task] = task
        while t is not None:
            if t._completion.fetch_add(-1) != 1:
                return
            t = self._finalize(t)

    def _finalize(self, task: Task) -> Optional[Task]:
        """All completion tokens dropped: the task and its whole subtree are
        done. Returns the parent (whose child token the caller must drop)."""
        san = self.san
        if san is not None:
            # before the (deferred) unregister: locked-mode release clocks
            # must be published before successors can become ready
            san.on_finalize(task)
        exp = self._explorer
        if exp is not None:
            exp.on_progress()  # finalize resets the no-progress watchdog
        if self._defer_unregister:
            # locked deps: conservative nesting — successors become ready
            # only once the full subtree completed
            self.deps.unregister_task(task, self._mailbox())
            self.tracer.event("dep.unregister", task.task_id)
        parent = task.parent
        group = task.group
        if task.exception is not None:
            with self._errors_lock:
                self._errors.append(task.exception)
        if group is not None:
            group._task_done(task)
        if self._live.fetch_add(-1) == 1:
            with self._quiescent_lock:  # re-check: a racing spawn re-armed
                if self._live.load() == 0:
                    self._quiescent.set()
        task.retire()  # stamp the recycling epoch before the pool can reuse
        self.pool.release(task)
        return parent

    def _run_task(self, task: Task, wid: int):
        if task.is_worksharing:
            # the scheduler hands a live worksharing descriptor to any idle
            # worker (possibly several at once): participate, don't run()
            self._run_worksharing(task, wid)
            return
        san = self.san
        group = task.group
        observed_epoch = None if group is None \
            else group._cancel_epoch.load()
        if group is not None and observed_epoch != task._cancel_epoch:
            # dropped at dequeue by the cancel token: skip the body but run
            # the full completion path below, so successors, taskwait and
            # pool recycling behave as if the body returned None
            self.tracer.event("task.cancel", task.task_id)
            self.counters.w(wid).tasks_cancelled += 1
            if san is not None:
                san.on_skip(task)
            task.skip()
        else:
            _current_task.t = task
            with self.tracer.span("task", task.task_id, task.name):
                task.start_ns = time.monotonic_ns()
                if san is not None:
                    # pass the epoch THIS dequeue decided on: a cancel
                    # landing after the check above legitimately overlaps
                    # the body
                    san.on_start(task, wid, group_epoch=observed_epoch)
                task.run()
                task.end_ns = time.monotonic_ns()
                self.counters.w(wid).on_task(task.end_ns - task.start_ns)
                if san is not None:
                    # before unregister: successors join this task's clock
                    # via the completion messages, which need the end tick
                    # in place
                    san.on_end(task)
            _current_task.t = None
        if not self._defer_unregister:
            # wait-free deps: TASK_DONE must flow at body completion; the
            # ASM child bits gate successors on nested children, while the
            # runtime-level completion tokens gate recycling on them
            self.deps.unregister_task(task, self._mailbox())
            self.tracer.event("dep.unregister", task.task_id)
        self._drop_token(task)

    # ---------------------------------------------------------- worksharing
    def _run_worksharing(self, ws, wid: Optional[int]) -> None:
        if not ws.ws_join():
            return  # closed: raced the last participant's finalize
        self._ws_participate(ws, wid)

    def _ws_participate(self, ws, wid: Optional[int]) -> None:
        """Claim and execute chunks until the cursor is exhausted (or the
        loop cancelled/errored), then leave; the LAST participant out runs
        :meth:`_finish_worksharing`. Caller must hold a successful
        ``ws_join``."""
        san = self.san
        exp = self._explorer
        tracer = self.tracer
        ctr = self.counters.w(wid)
        group = ws.group
        reduce_fn = ws.ws_reduce
        acc = ws.ws_reduce_init
        ran = 0
        if not ws.start_ns:
            ws.start_ns = time.monotonic_ns()  # first-ish participant
        prev = getattr(_current_task, "t", None)
        _current_task.t = ws  # nested spawns parent on the descriptor
        if san is not None:
            san.on_ws_join(ws, wid)
        try:
            while True:
                if group is not None and \
                        group._cancel_epoch.load() != ws._cancel_epoch:
                    # cancellation stops un-claimed chunks at the cursor; a
                    # chunk a peer is mid-way through is never interrupted
                    if ws.ws_cancel():
                        tracer.event("task.cancel", ws.task_id)
                    break
                if exp is not None:
                    # each claim is a scheduling decision point: concurrent
                    # participants may interleave between load and claim
                    exp.yield_point("ws.claim")
                idx = ws.ws_claim()
                if idx is None:
                    break
                tracer.event("ws.claim", idx)
                if san is not None:
                    san.on_ws_claim(ws, idx)
                lo, hi = ws.ws_bounds(idx)
                try:
                    if reduce_fn is not None:
                        acc = ws.ws_body(lo, hi, acc)
                    else:
                        ws.ws_body(lo, hi)
                except BaseException as e:  # first error wins, claims stop
                    ws.ws_record_error(e)
                    break
                ran += 1
                ctr.chunks_done += 1
        finally:
            _current_task.t = prev
            if san is not None:
                san.on_ws_leave(ws)
            partial = acc if (reduce_fn is not None and ran) else _NO_PARTIAL
            if ws.ws_leave(partial):
                self._finish_worksharing(ws, wid)

    def _finish_worksharing(self, ws, wid: Optional[int]) -> None:
        """Last participant out: merge the per-participant reduction
        partials ONCE, flip the descriptor to DONE, then run the exact
        completion tail of a normal task body (wait-free unregister +
        completion-token drop -> finalize/retire/release), so TaskGroup /
        taskwait / cancellation / pooling semantics hold unchanged."""
        result = None
        if ws.ws_reduce is not None:
            result = ws.ws_reduce_init
            for p in ws._ws_partials:
                result = ws.ws_reduce(result, p)
        self.ws_board.remove(ws)
        cancelled = ws._ws_cancelled
        box = ws._ws_result_box
        if box is not None:
            box.append(result)  # survives the descriptor's recycle
        ws.ws_finish(result)
        ws.end_ns = time.monotonic_ns()
        self.tracer.event("ws.finalize", ws.task_id)
        san = self.san
        if san is not None:
            san.on_ws_done(ws, cancelled=cancelled)
        if not self._defer_unregister:
            self.deps.unregister_task(ws, self._mailbox())
            self.tracer.event("dep.unregister", ws.task_id)
        self._drop_token(ws)

    # -------------------------------------------------------------- parking
    def _observe_arrival(self, now_ns: int):
        """Feed the park-timeout EWMA with the task inter-arrival time.
        Plain racy updates: the estimate is advisory and clamped by every
        reader, so a torn/lost sample only perturbs the smoothing."""
        last = self._last_arrival_ns
        self._last_arrival_ns = now_ns
        if last:
            dt = (now_ns - last) * 1e-9
            if 0.0 <= dt < 1.0:  # idle gaps are the park backoff's job
                self._ewma_arrival_s += self.park_ewma_alpha * \
                    (dt - self._ewma_arrival_s)

    def _park_timeout(self, n_timeouts: int) -> float:
        """Adaptive park timeout: proportional to observed inter-arrival
        (bursty fine-grained phases re-poll quickly), doubling per
        consecutive timeout (idle phases sleep long), clamped to
        [MIN, MAX]. The eventcount ablation keeps PR-1's fixed timeout."""
        if self.parking_kind != "slots":
            return _PARK_TIMEOUT_S
        base = max(self.park_ewma_mult * self._ewma_arrival_s,
                   self.park_timeout_min_s)
        return min(base * (1 << min(n_timeouts, 8)),
                   self.park_timeout_max_s)

    def _on_enqueue(self, numa_hint: int = 0,
                    worker_id: Optional[int] = None):
        """Scheduler wake hook: a task just became visible — wake one
        parked worker (or ``wake_fanout`` of them when the controller
        widened the fan-out for a bursty phase), preferring the task's
        NUMA node (or, for work-stealing, the worker whose deque
        received it)."""
        prefer_numa = numa_hint if self._n_numa > 1 else None
        fan = self.wake_fanout
        if fan > 1:
            woken = self._parking.wake_many(
                min(fan, self.n_workers), prefer_numa=prefer_numa) > 0
        else:
            woken = self._parking.wake_one(prefer_numa=prefer_numa,
                                           prefer_wid=worker_id)
        if woken:
            self.tracer.event("worker.wake", numa_hint)
        san = self.san
        if san is not None:
            san.on_enqueue_outcome(woken, self._parking.n_idle,
                                   self.scheduler.pending(), origin=self)

    def _worker(self, wid: int):
        _current_task.wid = wid
        parking = self._parking
        exp = self._explorer
        if exp is not None:
            exp.register(self._worker_id(wid))
        spins = 0
        n_timeouts = 0
        just_woken = False
        while not self._stop:
            if exp is not None:
                exp.yield_point("worker.dequeue")
            task = self.scheduler.get_ready_task(wid)
            if task is not None:
                just_woken = False
                spins = 0
                n_timeouts = 0
                self._run_task(task, wid)
                continue
            if just_woken:
                # woken from park but the first dequeue found nothing: the
                # wake was spurious (idle churn the fan-out clamp exists
                # to prevent) — counted so tests can assert zero
                parking.spurious.fetch_add(1)
                just_woken = False
            spins += 1
            if spins < _PARK_AFTER_SPINS and exp is None:
                # under exploration the idle spin phase is skipped: the
                # iterations are schedule-equivalent (pure re-polls), and
                # collapsing them keeps the POLLING->park window reachable
                # within a bounded decision budget
                self.tracer.event("worker.idle", wid)
                time.sleep(0)  # yield once before escalating to a park
                continue
            # futex protocol: publish POLLING, then re-poll — a producer
            # that missed the published state enqueued before our re-poll
            token = parking.begin_poll(wid)
            task = self.scheduler.get_ready_task(wid)
            if task is not None:
                parking.cancel_poll(wid)
                spins = 0
                n_timeouts = 0
                # wake chaining: single-wake producers wake one worker per
                # task; if more work is already queued while peers are
                # still parked, pass the wake along — unless the surplus is
                # already covered by in-flight (posted, unconsumed) wakes,
                # which would over-wake workers into an empty queue
                if parking.n_idle and \
                        self.scheduler.pending() > parking.n_pending_wakes:
                    self._on_enqueue()
                self._run_task(task, wid)
                continue
            if self._stop:
                parking.cancel_poll(wid)
                break
            if exp is not None:
                # the POLLING->PARKED window: a wake posted right here is
                # exactly what the futex re-poll protocol must tolerate
                exp.yield_point("worker.prepark")
            self.tracer.event("worker.park", wid)
            san = self.san
            if parking.park(wid, token, self._park_timeout(n_timeouts)):
                n_timeouts = 0
                spins = 0  # woken: poll, then spin briefly before re-park
                just_woken = True
                if san is not None:
                    san.on_worker_woken(wid)
            else:
                n_timeouts += 1
                spins = _PARK_AFTER_SPINS  # timed out: skip the spin phase
                if san is not None:
                    san.on_park_timeout(wid, self.scheduler.pending(),
                                        origin=self)
        if exp is not None:
            exp.thread_exit()

    # ---------------------------------------------------------------- sync
    def taskwait(self, task: Union[Task, TaskRef],
                 timeout: Optional[float] = None) -> bool:
        """Wait for the task's body to finish. With a TaskRef (stamped at
        spawn) recycling is fully detected: returns True immediately when
        the logical task already finished, never blocking on the object's
        next occupant. With a bare Task the generation is captured HERE, so
        recycling during the wait is detected (no orphaned-event hang), but
        a recycle that happened before the call makes this wait on the new
        occupant — spawn with handle=True when that race is possible."""
        if isinstance(task, TaskRef):
            t, gen = task.task, task.generation
        else:
            t, gen = task, task.generation
        ok = self._taskwait(t, gen, timeout)
        san = self.san
        if ok and san is not None:
            san.on_taskwait(t, gen)  # awaited task happens-before waiter
        return ok

    def _taskwait(self, t: Task, gen: int,
                  timeout: Optional[float]) -> bool:
        def finished() -> bool:
            return t.generation != gen or t.state == DONE

        if finished():
            return True
        ev = t.wait_handle()
        if finished():  # completion may have raced wait_handle installation
            return True
        exp = self._explorer
        if exp is not None:
            # serialized wait: the policy (not the wall clock) decides when
            # a timed wait expires; target/task feed the self-cycle check
            st = exp.wait_until(finished, kind="taskwait",
                                label=f"taskwait({t.name or t.task_id})",
                                task=current_task(), target=t,
                                timed=timeout is not None)
            if st != "disabled":
                return finished()
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            slice_s = _PARK_TIMEOUT_S
            if deadline is not None:
                slice_s = min(slice_s, deadline - time.monotonic())
                if slice_s <= 0:
                    return finished()
            if ev.wait(slice_s):
                # the event belongs to whatever occupies the object now; our
                # logical task is done either way (set, or generation moved)
                return True
            if finished():
                return True

    def barrier(self, timeout: Optional[float] = None) -> bool:
        """Wait until all spawned tasks (incl. nested) fully finished."""
        exp = self._explorer
        if exp is not None:
            st = exp.wait_until(self._quiescent.is_set, kind="barrier",
                                label="barrier", task=current_task(),
                                timed=timeout is not None)
            if st != "disabled":
                return self._quiescent.is_set()
        return self._quiescent.wait(timeout)

    # ---------------------------------------------------------------- stats
    def stats(self) -> dict:
        return {"pool": self.pool.stats,
                "pending": self.scheduler.pending(),
                "live": self._live.load(),
                "parked": self._parking.n_parked,
                "parks": self._parking.parks.load(),
                "wakes": self._parking.wakes.load(),
                "spurious_wakes": self._parking.spurious.load(),
                "mailboxes": self._mb_pool.stats,
                "scheduler": {"kind": self.scheduler.kind,
                              "policy": self.scheduler.policy,
                              "switches": self.scheduler.switches},
                "counters": self.counters.snapshot()}


class RuntimeCluster:
    """N independent TaskRuntimes coordinated as one unit.

    This is the in-process scale-out primitive behind the sharded serve
    path (repro.serve.router): each member runs its own workers, scheduler
    and dependency space — no cross-runtime address aliasing, callers
    namespace shared logical addresses themselves — while the cluster
    provides what must be common:

    * one Tracer, so per-shard events land in one event stream;
    * one TaskSanitizer (when sanitizing), so handoffs *between* runtimes
      (e.g. session migration) are checked in a single clock domain;
    * one ScheduleExplorer (when exploring), with members named
      ``{name}{i}`` so their worker registrations don't collide;
    * aggregated shutdown: every member is shut down even if an earlier
      one raises, errors combine into one exception, and a shared
      sanitizer is flushed/checked exactly once, at the end.

    ``task_group()`` returns a TaskGroup bound to member 0 that any
    member's spawn() may target — groups only need a home runtime for
    cancel bookkeeping, membership is cross-runtime (the migration tasks
    in repro.serve.router rely on this).
    """

    def __init__(self, n_runtimes: int, *, n_workers: int = 2,
                 tracer: Optional[Tracer] = None,
                 sanitize: Union[bool, str, None] = None,
                 explore=None, name: str = "rt", **runtime_kwargs):
        if n_runtimes < 1:
            raise ValueError("n_runtimes must be >= 1")
        self.name = name
        self.tracer = tracer or Tracer(enabled=False)
        if sanitize is None:
            env = os.environ.get("REPRO_SANITIZE", "")
            sanitize = "report" if env == "report" \
                else env not in ("", "0", "false")
        self.san = None
        if sanitize:
            from repro.analyze.tsan import TaskSanitizer
            if isinstance(sanitize, TaskSanitizer):
                self.san = sanitize
            else:
                self.san = TaskSanitizer(
                    raise_on_shutdown=(sanitize != "report"))
        if explore is not None and explore is not False:
            # normalize to ONE explorer instance before fan-out — passing
            # explore=True through would give each member a private explorer
            from repro.analyze.explore import (ScheduleExplorer,
                                               SchedulePolicy)
            if isinstance(explore, SchedulePolicy):
                explore = ScheduleExplorer(explore)
            elif not isinstance(explore, ScheduleExplorer):
                explore = ScheduleExplorer()
        self.runtimes: list[TaskRuntime] = [
            TaskRuntime(n_workers=n_workers, tracer=self.tracer,
                        sanitize=self.san if self.san is not None else False,
                        explore=explore, name=f"{name}{i}", **runtime_kwargs)
            for i in range(n_runtimes)]
        self._started = False

    def __len__(self) -> int:
        return len(self.runtimes)

    def __getitem__(self, i: int) -> TaskRuntime:
        return self.runtimes[i]

    def start(self) -> "RuntimeCluster":
        if self._started:
            return self
        self._started = True
        for rt in self.runtimes:
            rt.start()
        return self

    def shutdown(self, wait: bool = True):
        """Shut down every member; raise one combined exception at the end.

        A member failing to shut down must not strand the others' worker
        threads, so each member is attempted regardless; task errors from
        all members attach as siblings of the first. The shared sanitizer
        runs its end-of-run check once, after every member stopped."""
        errs: list[BaseException] = []
        for rt in self.runtimes:
            try:
                rt.shutdown(wait=wait)
            except BaseException as e:  # noqa: BLE001 - aggregated below
                errs.append(e)
        self._started = False
        san = self.san
        if san is not None:
            san.flush_report()
        if errs:
            raise _attach_siblings(errs)
        if san is not None and san.raise_on_shutdown:
            san.check()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(wait=exc[0] is None)

    def barrier(self, timeout: Optional[float] = None) -> bool:
        """Quiescence across every member runtime."""
        deadline = None if timeout is None else time.monotonic() + timeout
        for rt in self.runtimes:
            left = None if deadline is None \
                else max(0.0, deadline - time.monotonic())
            if not rt.barrier(timeout=left):
                return False
        return True

    def collect(self) -> int:
        return sum(rt.collect() for rt in self.runtimes)

    def task_group(self, name: str = "",
                   cancel_on_error: bool = False) -> TaskGroup:
        return self.runtimes[0].task_group(name,
                                           cancel_on_error=cancel_on_error)

    def stats(self) -> dict:
        per = [rt.stats() for rt in self.runtimes]
        return {"runtimes": per,
                "pending": sum(s["pending"] for s in per),
                "live": sum(s["live"] for s in per)}
