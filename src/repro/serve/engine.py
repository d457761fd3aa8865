"""Continuous-batching serving engine orchestrated by the paper's runtime.

Request lifecycle as a task graph (resources in parens):
  admit      WRITES (slot, i)           — claims a KV slot for the request
  prefill    RW (slot, i)               — runs the model prefill, fills the
                                          slot's KV cache, emits first token
  decode     RW "decode"  READS slots   — ONE batched decode task per
                                          iteration covers all active slots
                                          (continuous batching); finished
                                          slots retire inside the task
  emit       per-request callback

The decode loop is the paper's single-creator regime: the loop task spawns
the next decode task; admits/prefills arrive concurrently from request
threads, and the ASM dependency system interleaves slot claims with the
batched decode without a global scheduler lock.

Scale-out split (see docs/SERVING.md): :class:`EngineCore` is the
model-agnostic half — admission queue, slot lifecycle, decode chain,
per-hash-slot session state and the seal/drain hooks migration needs.
:class:`ServeEngine` adds the jax model (a compiled prefill per prompt-length
bucket that splices into the KV cache, and the batched decode) and is what
a single-runtime deployment instantiates. ``repro.serve.shard`` subclasses
the core with a simulated backend whose decode *sleeps* (models device
compute that releases the GIL, like a dispatched XLA kernel) so shard
scaling is measurable in-process; ``repro.serve.router`` composes N cores
into one sharded engine.

When the engine runs with ``shard_id`` set, its dependency addresses are
namespaced per shard — N engines sharing one process (RuntimeCluster) must
not alias each other's ("slot", i) addresses in a shared sanitizer's shadow
state. Session state is the one deliberate exception: it is keyed
("sess", h) globally because ownership of a hash slot *moves* between
shards; its cross-shard ordering comes from the sanitizer's sync channels
(the engine-side lock + the seal->drain handoff), not from the dependency
system.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from typing import Callable, Optional

import numpy as np

from repro.models.common import NULL_SHARDER, cast_params, dtype_of


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 16
    id: int = 0
    on_token: Optional[Callable] = None
    tokens: list = dataclasses.field(default_factory=list)
    done_event: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    # scale-out fields (see repro.serve.router)
    key: Optional[str] = None       # affinity key (session / prefix-cache)
    hslot: Optional[int] = None     # affinity_hash(key) when key is set
    shard_id: Optional[int] = None  # shard that admitted the request
    # host monotonic stamps, always on: submitted, popped into a slot,
    # prefill body entered, retired
    submit_ns: int = 0
    slot_ns: int = 0
    prefill_ns: int = 0
    done_ns: int = 0
    rejected: bool = False
    _done_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)

    def finish(self) -> bool:
        """Set done_event exactly once; True only for the first caller —
        the accounting primitive behind the zero-double-completion
        guarantee (a second completion is a router/migration bug and is
        counted, not silently absorbed)."""
        with self._done_lock:
            if self.done_event.is_set():
                return False
            self.done_event.set()
            return True


class AdmissionQueue:
    """Bounded admission FIFO. ``limit <= 0`` means unbounded (the legacy
    single-engine mode); a sharded deployment always bounds it so a burst
    becomes queueing delay on the shard and, past the bound, shedding at
    the router — never an unbounded backlog.

    ``lock`` is public: the engine runs compound check-and-move sequences
    (admission guard + append, pop + admitted-table insert) under it so
    that seal/drain accounting never observes a request in neither
    structure."""

    def __init__(self, limit: int = 0):
        self.limit = limit
        self.lock = threading.Lock()
        self._q: collections.deque = collections.deque()

    def try_append(self, req: Request, guard=None) -> bool:
        """Append unless full or ``guard()`` (evaluated under the queue
        lock) refuses; False means the caller must redirect/shed."""
        with self.lock:
            if guard is not None and not guard():
                return False
            if 0 < self.limit <= len(self._q):
                return False
            self._q.append(req)
            return True

    def drain(self) -> list:
        with self.lock:
            out = list(self._q)
            self._q.clear()
        return out

    @property
    def depth(self) -> int:
        return len(self._q)

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)


class EngineCore:
    """Model-agnostic continuous-batching core (see module docstring).

    Subclasses implement ``_prefill_exec(req, slot) -> first_token`` and
    ``_decode_exec(live_slots) -> next_token_by_slot``."""

    def __init__(self, runtime, *, n_slots: int = 4, max_seq: int = 256,
                 shard_id: Optional[int] = None, queue_limit: int = 0):
        self.rt = runtime
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.shard_id = shard_id
        self.pos = np.zeros(n_slots, np.int32)        # next cache position
        self.budget = np.zeros(n_slots, np.int32)     # remaining new tokens
        self.active: list[Optional[Request]] = [None] * n_slots
        self._free = list(range(n_slots))
        self._free_lock = threading.Lock()
        self._queue = AdmissionQueue(limit=queue_limit)
        # admitted requests whose prefill has not completed yet (slot ->
        # Request): stop(drain=False) must release these waiters too — a
        # cancelled prefill never runs, so it never reaches self.active
        self._admitted: dict[int, Request] = {}
        self._admitted_lock = threading.Lock()
        self._stop = False
        # all engine tasks (prefills + decode iterations) run in one
        # TaskGroup: completion tracking without retaining pooled Task
        # objects (holding a non-retained Task across its completion is a
        # use-after-recycle; see the TaskRuntime lifecycle contract).
        # cancel_on_error: the first failing engine task cancels the group,
        # which stops the self-respawning decode chain and drops queued
        # engine tasks instead of letting errors pile up per iteration
        self.group = runtime.task_group("serve", cancel_on_error=True)
        # ANY cancel — stop(drain=False) or the first task error — must
        # release every blocked client, not just the explicit-stop path
        self.group.on_cancel = self._release_waiters
        self._next_id = 0
        self._id_lock = threading.Lock()
        self.stats = {"prefills": 0, "decode_iters": 0, "tokens": 0,
                      "completed": 0, "rejected": 0, "double_completed": 0}
        # per-hash-slot session state (prefix-cache metadata), written by
        # prefill bodies and moved wholesale by migration. Guarded by an
        # engine-side lock the dependency system never sees — ordering is
        # taught to tasksan through a sync channel (docs/SERVING.md)
        self.sessions: dict[int, dict] = {}
        self._sess_lock = threading.Lock()
        # migration seal/drain handshake; _sealed is guarded by the
        # admission queue's lock so the admission guard and seal() agree
        self._sealed: set[int] = set()
        self._drain_events: dict[int, threading.Event] = {}
        # completion hook + latency ring for the router / servebench
        self.on_complete: Optional[Callable[[Request], None]] = None
        self.latencies_us: collections.deque = collections.deque(maxlen=4096)

    # ------------------------------------------------------------ addresses
    # Dependency addresses are shard-namespaced: N engines in one process
    # sharing a sanitizer/tracer must not alias each other's slots.
    def _addr(self, name: str):
        return name if self.shard_id is None else (name, self.shard_id)

    def _slot_addr(self, i: int):
        return ("slot", i) if self.shard_id is None \
            else ("slot", self.shard_id, i)

    def _decode_reads(self) -> list:
        # the module contract: decode READS every slot — prefills RW their
        # slot, so the dependency system serializes a slot's prefill against
        # decode iterations instead of racing on the shared cache
        return [self._addr("params")] + [self._slot_addr(i)
                                         for i in range(self.n_slots)]

    # ---------------------------------------------------------- model hooks
    def _prefill_exec(self, req: Request, slot: int) -> int:
        raise NotImplementedError

    def _decode_exec(self, live: list) -> np.ndarray:
        raise NotImplementedError

    # ---------------------------------------------------------- lifecycle
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               on_token=None, *, key=None) -> Request:
        with self._id_lock:
            rid = self._next_id
            self._next_id += 1
        req = Request(np.asarray(prompt, np.int32), max_new_tokens,
                      id=rid, on_token=on_token, key=key)
        if key is not None:
            from repro.dist.partitioning import affinity_hash
            req.hslot = affinity_hash(key)
        req.submit_ns = time.monotonic_ns()
        if not self.offer(req):
            if not self.group.cancelled:
                # bounded queue full / hash slot sealed: a standalone engine
                # has nowhere to redirect, so the request sheds here
                req.rejected = True
                self.stats["rejected"] += 1
                self.rt.tracer.event("serve.reject", self.shard_id or 0)
            req.finish()
        return req

    def offer(self, req: Request) -> bool:
        """Admit one request into the queue. False when refused (engine
        cancelled, queue at its bound, or the request's hash slot is sealed
        for migration) — the router redirects or sheds on refusal."""
        req.shard_id = self.shard_id

        def _admissible() -> bool:
            if self.group.cancelled:  # terminal engine never drains the
                return False          # queue again: don't grow it
            return req.hslot is None or req.hslot not in self._sealed

        if not self._queue.try_append(req, guard=_admissible):
            return False
        tracer = self.rt.tracer
        tracer.event("serve.admit", self.shard_id or 0)
        tracer.event("serve.depth", self._queue.depth)
        return True

    @property
    def load(self) -> int:
        """Queue depth + occupied slots: the router's balance metric."""
        with self._free_lock:
            busy = self.n_slots - len(self._free)
        return self._queue.depth + busy

    def _admit(self):
        """Move queued requests into free slots (spawns prefill tasks)."""
        while not self.group.cancelled:
            with self._free_lock:
                if not self._free:
                    return
            # pop + admitted-insert under the queue lock: drain accounting
            # (_hslot_quiet) must never observe a request in neither place
            with self._queue.lock:
                if not self._queue._q:
                    return
                with self._free_lock:
                    if not self._free:
                        return
                    slot = self._free.pop(0)
                req = self._queue._q.popleft()
                req.slot_ns = time.monotonic_ns()
                with self._admitted_lock:
                    self._admitted[slot] = req
            # detached: prefills are admitted from inside a decode task but
            # are not nested work of that iteration. The commutative "cache"
            # access makes concurrent prefills mutually exclusive (each
            # prefill program takes the whole cache tree donated and returns
            # it: a read-modify-write) while leaving their order free —
            # per-slot addresses alone would let two prefills interleave and
            # lose one slot's KV.
            t = self.group.spawn(self._prefill_task, (req, slot),
                                 name=f"prefill:{req.id}", detached=True,
                                 rw=[self._slot_addr(slot)],
                                 reads=[self._addr("params")],
                                 commutative=[self._addr("cache")])
            if t is None:  # group cancelled concurrently: return the slot
                with self._admitted_lock:
                    self._admitted.pop(slot, None)
                with self._free_lock:
                    self._free.append(slot)
                req.finish()  # never admitted; unblock its waiter
                return

    def _prefill_task(self, req: Request, slot: int):
        req.prefill_ns = time.monotonic_ns()
        with self.rt.tracer.span("serve.prefill", req.id):
            first = self._prefill_exec(req, slot)
        self.budget[slot] = req.max_new_tokens
        req.tokens.append(first)
        if req.on_token:
            req.on_token(first)
        self.touch_session(req)
        self.active[slot] = req
        with self._admitted_lock:  # visible in active BEFORE leaving here:
            self._admitted.pop(slot, None)  # stop() always sees one of them
        self.stats["prefills"] += 1

    def _decode_iter(self):
        # the spans take the id of the task span around this body: the
        # decode task's
        tracer = self.rt.tracer
        live = [i for i, r in enumerate(self.active) if r is not None]
        if live:
            with tracer.span("serve.decode"):
                nxt = self._decode_exec(live)
            with tracer.span("serve.emit"):
                for i in live:
                    req = self.active[i]
                    tok = int(nxt[i])
                    req.tokens.append(tok)
                    self.stats["tokens"] += 1
                    if req.on_token:
                        req.on_token(tok)
                    self.pos[i] += 1
                    self.budget[i] -= 1
                    if self.budget[i] <= 0 or \
                            self.pos[i] >= self.max_seq - 1:
                        self.active[i] = None
                        with self._free_lock:
                            self._free.append(i)
                        self._retire(req)
            self.stats["decode_iters"] += 1
        with tracer.span("serve.admit"):
            self._admit()
        if not self._stop:
            # idle backoff is a wall-clock pause: skipped under the
            # schedule explorer, where it would stall the serialized world
            if not live and self.rt._explorer is None:
                with tracer.span("serve.idle"):
                    time.sleep(0.002)
            # detached: the loop respawns itself — parenting iteration N+1
            # on N would chain completion tokens forever and pin every
            # decode Task in memory until stop()
            self.group.spawn(self._decode_iter, name="decode.loop",
                             detached=True, rw=[self._addr("decode")],
                             reads=self._decode_reads())

    def _retire(self, req: Request):
        req.done_ns = time.monotonic_ns()
        if req.finish():
            self.stats["completed"] += 1
            if req.submit_ns:
                lat_us = (req.done_ns - req.submit_ns) // 1000
                self.latencies_us.append(lat_us)
                self.rt.tracer.event("serve.complete", lat_us)
            cb = self.on_complete
            if cb is not None:
                cb(req)
        else:
            self.stats["double_completed"] += 1
        self._check_drain(req.hslot)

    # ------------------------------------------------------------ sessions
    @staticmethod
    def _sess_chan(h: int):
        """Sanitizer sync channel for hash slot ``h``'s session state.

        Keyed per hash slot and GLOBAL — like the ("sess", h) address it
        orders — because ownership of ``h`` moves between engines: the
        last write an engine makes (including the drop at migration
        commit, or the destination cleanup when an install fails) must be
        visible to whichever engine touches ``h`` next, and a per-engine
        channel can't carry clocks across that handoff."""
        return ("serve.sess", h)

    def touch_session(self, req: Request) -> int:
        """Record the request against its hash-slot session (prefill body).
        Returns the prior hit count (a prefix-cache hit indicator)."""
        if req.key is None:
            return 0
        h = req.hslot
        san = self.rt.san
        with self._sess_lock:
            if san is not None:
                san.on_sync_acquire(self._sess_chan(h))
                san.on_manual_access(("sess", h))
            sess = self.sessions.setdefault(h, {})
            ent = sess.setdefault(req.key, {"hits": 0, "prefix": 0})
            hits = ent["hits"]
            ent["hits"] += 1
            ent["prefix"] = max(ent["prefix"], int(len(req.prompt)))
            if san is not None:
                san.on_sync_release(self._sess_chan(h))
        return hits

    def export_session(self, h: int) -> dict:
        """Deep-copy hash slot ``h``'s session state (migration export).
        The source keeps its copy until ``drop_session`` at commit, so an
        aborted migration leaves the source authoritative."""
        san = self.rt.san
        with self._sess_lock:
            if san is not None:
                san.on_sync_acquire(self._sess_chan(h))
                san.on_manual_access(("sess", h), "r")
            state = {k: dict(v) for k, v in self.sessions.get(h, {}).items()}
            if san is not None:
                san.on_sync_release(self._sess_chan(h))
        return state

    def install_session(self, h: int, state: dict) -> None:
        san = self.rt.san
        with self._sess_lock:
            if san is not None:
                san.on_sync_acquire(self._sess_chan(h))
                san.on_manual_access(("sess", h))
            if state:
                merged = self.sessions.setdefault(h, {})
                for k, v in state.items():
                    merged[k] = dict(v)
            if san is not None:
                san.on_sync_release(self._sess_chan(h))

    def drop_session(self, h: int) -> None:
        san = self.rt.san
        with self._sess_lock:
            if san is not None:
                san.on_sync_acquire(self._sess_chan(h))
                san.on_manual_access(("sess", h))
            self.sessions.pop(h, None)
            if san is not None:
                san.on_sync_release(self._sess_chan(h))

    # ------------------------------------------------------- seal / drain
    def seal(self, h: int) -> threading.Event:
        """Stop admitting requests for hash slot ``h`` (offers are refused;
        the router parks them) and return an Event that sets once every
        already-admitted request for ``h`` — queued, in prefill, or
        decoding — has retired. Migration export waits on it: after it
        fires, no task on this shard will touch ``h``'s session again."""
        ev = self._drain_events.setdefault(h, threading.Event())
        with self._queue.lock:
            self._sealed.add(h)
        self._check_drain(h)
        return ev

    def unseal(self, h: int) -> None:
        with self._queue.lock:
            self._sealed.discard(h)
        self._drain_events.pop(h, None)

    def _hslot_quiet(self, h: int) -> bool:
        with self._queue.lock:
            if any(r.hslot == h for r in self._queue._q):
                return False
        with self._admitted_lock:
            if any(r.hslot == h for r in self._admitted.values()):
                return False
        return all(r is None or r.hslot != h for r in self.active)

    def _check_drain(self, h: Optional[int]) -> None:
        if h is None or h not in self._sealed:
            return
        ev = self._drain_events.get(h)
        if ev is None or ev.is_set():
            return
        if self._hslot_quiet(h):
            san = self.rt.san
            if san is not None:
                # the drained handoff: the last retiring task publishes,
                # the migration export (on another thread, possibly another
                # runtime) observes before touching ("sess", h)
                san.on_sync_release(("serve.drain", self.shard_id, h))
            ev.set()

    # ------------------------------------------------------------ control
    def start(self):
        self.group.spawn(self._decode_iter, name="decode.loop",
                         detached=True, rw=[self._addr("decode")],
                         reads=self._decode_reads())
        return self

    def stop(self, drain: bool = True, timeout: float = 60.0) -> bool:
        """Stop the decode loop. With drain=True, block until every engine
        task (in-flight prefills + the final decode iteration) fully
        finished, re-raising the first task error if any occurred. With
        drain=False, cancel the engine's TaskGroup instead: no further
        spawns are admitted, still-queued engine tasks (including the next
        decode iteration) are dropped at dequeue, and only the task already
        mid-body runs to completion — the engine is terminal after this.
        Every unfinished request (queued, admitted or mid-decode) gets its
        done_event set so no client blocks in wait(); callers inspect
        req.tokens for whatever was produced before the cancel. The same
        release runs when the group self-cancels on a task error."""
        self._stop = True
        if drain:
            return self.group.wait(timeout=timeout)
        self.group.cancel()  # -> on_cancel -> _release_waiters (once)
        return True

    def _release_waiters(self):
        """Unblock every client of an unfinished request (group.on_cancel)."""
        for req in self._queue.drain():
            req.finish()
        with self._admitted_lock:  # admitted, prefill dropped by the cancel
            admitted = list(self._admitted.values())
        for req in admitted:
            req.finish()
        for req in list(self.active):
            if req is not None:
                req.finish()

    def wait(self, req: Request, timeout: float = 120.0) -> bool:
        exp = self.rt._explorer
        if exp is not None:
            st = exp.wait_until(req.done_event.is_set, kind="serve-wait",
                                label=f"serve.wait:{req.id}", timed=True)
            if st != "disabled":
                return req.done_event.is_set()
        return req.done_event.wait(timeout)


class ServeEngine(EngineCore):
    """The jax-model engine: EngineCore + the compiled prefill and batched
    decode. Single-runtime deployments use this directly; the sharded
    router drives one model engine (or simulated core) per shard.

    The weights are held in ``cfg.dtype`` (cast once here, so the per-call
    cast in ``forward`` is a no-op) and reach each jitted program as an
    argument: a closed-over array would be baked into the program as a
    constant. Both programs take the batched cache donated and return it
    updated in place. A step whose logits are not finite raises
    ``FloatingPointError``, which cancels the engine and surfaces from
    ``stop()``.

    A prefill is one program per prompt-length bucket (``prefill_bucket``):
    the forward, the first token from the last real position's logits
    alone, and the splice of the prompt's cache into its slot. A dense
    model's prompt is padded at its end to the bucket: attention is
    causal, so no real position sees a pad, and decode overwrites the pad
    rows before its mask (``k <= pos``) reaches them. Other families
    compile at the exact length: pads would run through an SSM's state
    and change an MoE layer's capacity."""

    def __init__(self, cfg, params, runtime, *, n_slots: int = 4,
                 max_seq: int = 256, sharder=NULL_SHARDER, greedy=True,
                 shard_id: Optional[int] = None, queue_limit: int = 0):
        super().__init__(runtime, n_slots=n_slots, max_seq=max_seq,
                         shard_id=shard_id, queue_limit=queue_limit)
        import jax

        from repro.models import api as mapi
        self.cfg = cfg
        self.params = cast_params(params, dtype_of(cfg))
        self.sh = sharder
        # batched caches: one cache tree with batch dim = n_slots
        self.cache = mapi.init_cache(cfg, n_slots, max_seq)
        self._decode_fn = jax.jit(
            functools.partial(_decode_batch, cfg, sharder),
            donate_argnums=(1,))
        self._prefill_fn = jax.jit(
            functools.partial(_prefill_splice, cfg, sharder, n_slots),
            donate_argnums=(1,))
        self._prefill_buckets: set = set()
        self.stats["prefill_programs"] = 0

    # ---------------------------------------------------------- core hooks
    def _prefill_exec(self, req: Request, slot: int) -> int:
        import jax
        tracer = self.rt.tracer
        L = min(len(req.prompt), self.max_seq - req.max_new_tokens - 1)
        S = prefill_bucket(self.cfg, L, self.max_seq)
        tokens = np.zeros((1, S), np.int32)
        tokens[0, :L] = req.prompt[:L]
        tracer.event("serve.prefill.pad", S - L)
        self._prefill_buckets.add(S)  # jit builds a program per shape
        self.stats["prefill_programs"] = len(self._prefill_buckets)
        with tracer.span("serve.prefill.forward"):
            first, finite, self.cache = self._prefill_fn(
                self.params, self.cache, tokens, np.int32(L), np.int32(slot))
        with tracer.span("serve.prefill.sync"):
            first, finite = jax.device_get((first, finite))
            if not finite:
                raise FloatingPointError("prefill produced non-finite logits")
        self.pos[slot] = L
        return int(first)

    def _decode_exec(self, live: list) -> np.ndarray:
        import jax.numpy as jnp
        tracer = self.rt.tracer
        with tracer.span("serve.decode.inputs"):
            toks = np.zeros((self.n_slots, 1), np.int32)
            for i in live:
                toks[i, 0] = self.active[i].tokens[-1]
            # per-slot cache positions (continuous batching): idle slots
            # write harmlessly into their own stale position
            toks, pos = jnp.asarray(toks), jnp.asarray(self.pos)
        with tracer.span("serve.decode.launch"):
            nxt, finite, self.cache = self._decode_fn(
                self.params, self.cache, toks, pos)
        with tracer.span("serve.decode.sync"):
            finite = np.asarray(finite)
            bad = [i for i in live if not finite[i]]
            if bad:
                raise FloatingPointError(
                    f"decode produced non-finite logits in slots {bad}")
            return np.asarray(nxt)


def _decode_batch(cfg, sh, params, cache, tokens, pos):
    """One batched decode step -> (greedy next token, logits finite, cache)
    per slot. Module-level so that jit sees every array as an argument."""
    import jax.numpy as jnp

    from repro.models import api as mapi
    logits, _, new_cache = mapi.forward(cfg, params, {"tokens": tokens}, sh,
                                        mode="decode", cache=cache,
                                        cache_pos=pos)
    last = logits[:, -1, :]
    return (jnp.argmax(last, axis=-1), jnp.all(jnp.isfinite(last), axis=-1),
            new_cache)


PREFILL_BUCKET = 256  # a dense prompt is padded up to a multiple of this


def prefill_bucket(cfg, length: int, max_seq: int) -> int:
    """The padded length a prompt of ``length`` tokens is prefilled at: the
    next multiple of PREFILL_BUCKET, at most ``max_seq``, for a dense
    model; the exact length for every other family."""
    if cfg.family != "dense":
        return length
    return min(-(-length // PREFILL_BUCKET) * PREFILL_BUCKET, max_seq)


def _prefill_splice(cfg, sh, n_slots, params, cache, tokens, length, slot):
    """Prefill one prompt, tokens (1, bucket) with ``length`` real tokens,
    into slot ``slot`` of the batched cache -> (greedy first token, its
    logits finite, cache). The final norm and LM head run on position
    ``length - 1`` alone. Each leaf with a slot axis (ndim >= 3, axis 1 of
    size n_slots) takes the prompt's rows at (0, slot, 0, ...); the rest
    pass through. Module-level so that jit sees every array as an
    argument."""
    import jax
    import jax.numpy as jnp

    from repro.models import api as mapi
    logits, _, seq = mapi.forward(cfg, params, {"tokens": tokens}, sh,
                                  mode="prefill", logits_at=length - 1)

    def splice(dst, src):
        if dst.ndim >= 3 and src.shape[0] == dst.shape[0] and \
                dst.shape[1] == n_slots:
            # (L, n_slots, T, ...) <- (L, 1, S, ...)
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype),
                (0, slot) + (0,) * (dst.ndim - 2))
        return dst

    last = logits[0, -1]
    return (jnp.argmax(last), jnp.all(jnp.isfinite(last)),
            jax.tree_util.tree_map(splice, cache, seq))
