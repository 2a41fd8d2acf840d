"""Batched serving engine: continuous-batching decode over fixed slots.

Requests occupy slots of a fixed-capacity batch; each engine step decodes
one token for every live slot (one jit'd decode_fn call — padding slots
ride along). Prefill fills a slot's cache region. Greedy or temperature
sampling. The same engine drives the serve_lm example and the serving
integration tests.

SAMPLING IS A PER-REQUEST STREAM, not a shared sequential one: token t of
request ``rid`` is drawn from ``fold_in(fold_in(PRNGKey(seed), rid), t)``
(`_sample_per_request`). A shared split-per-engine-step key would make a
request's tokens depend on unrelated traffic interleaving — admission
order, co-tenants, slot placement — so an evicted request could never be
REPLAYED bit-identically. With per-request streams a request's output is
a pure function of (engine seed, rid, prompt, model), which is the
invariant the fault-tolerant supervision layer
(`serve/engine_fault.py:FaultTolerantEngine`) rests on: kill a slot
mid-decode, re-prefill prompt + generated prefix elsewhere, and the
continuation is bit-identical (property-tested in
`tests/test_engine_determinism.py`).

The dispatch path is factored into overridable hooks (`_admissible`,
`_pre_dispatch_prefill`, `_prefill_dispatch`, `_decode_dispatch`,
`_slot_retires`, `_on_retire`, `_on_finish`) so the supervision layer can
inject faults, heartbeats, and eviction without duplicating the
batching/bucketing logic. Typed errors at the admission boundary:
`PromptTooLong` (a prompt the cache cannot hold is rejected at `submit`,
never mid-bucket), `EngineStalled` (`run_to_completion` exhausted
``max_steps`` with work still queued/live — carries the unfinished rids
instead of silently dropping them).

`ColumnScheduler` is the admission policy for the OTHER traffic class the
repo serves — continuous biosignal streams: independent streams are placed
on distinct column replicas (devices), the multi-tenant complement of
sharding one stream across all columns (`StreamConfig.n_columns`). With a
`StreamTelemetry` attached it is load-aware: placement by least MEASURED
windows/s (stream count is only the cold-start fallback), a `rebalance`
work-stealing pass that re-pins streams when the max/min column-load
ratio blows a threshold, and `deal_weights` feeding measured per-column
rates into the non-uniform frame deal.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_cache
from repro.runtime.fault import (HeartbeatMonitor, InsufficientHealthyWorkers,
                                 StragglerDetector)
# the typed errors live in the serve/errors.py taxonomy (ServeError
# root) and are re-exported from here, their historical home
from repro.serve.errors import (EngineStalled, InsufficientPages,
                                PagedCacheUnsupported,  # noqa: F401 (re-export)
                                PromptTooLong)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # set by the supervision layer when the request was evicted from a
    # faulty slot and requeued for replay (serve/engine_fault.py)
    replayed: bool = False


@functools.partial(jax.jit, static_argnums=(3,))
def _merge_cache_leaves(old_leaves, new_leaves, mask, axes):
    """Slot-masked cache merge, one fused jit call for the whole tree.

    ``mask`` is a (slots,) bool vector of admitted slots; ``axes`` the
    per-leaf slot-axis indices (static — read off the cache schema's
    named "batch" axis, see `Engine.__init__`). A mask instead of an
    index list keeps the trace shape fixed across admission patterns, so
    every engine sharing a cache shape reuses ONE compilation — the
    eager per-leaf gather/scatter this replaces dominated admission
    wall time (~6ms per merge on CPU for a 2-leaf cache)."""
    out = []
    for ax, old, new in zip(axes, old_leaves, new_leaves):
        shape = [1] * old.ndim
        shape[ax] = old.shape[ax]
        out.append(jnp.where(mask.reshape(shape), new, old))
    return out


@jax.jit
def _sample_per_request(base_key, rids, steps, logits):
    """Batched per-request-stream categorical sample.

    Slot s draws from ``fold_in(fold_in(base_key, rids[s]), steps[s])``
    where ``steps[s]`` is the token's index WITHIN its request — the key
    depends only on (engine seed, rid, step), never on which slot the
    request occupies, what else is in flight, or how many engine steps
    have passed. That placement-invariance is what makes evicted-request
    replay bit-identical (`serve/engine_fault.py`). Callers divide the
    logits by temperature; dead slots ride along and are ignored."""
    def one(rid, step, lg):
        k = jax.random.fold_in(jax.random.fold_in(base_key, rid), step)
        return jax.random.categorical(k, lg)
    return jax.vmap(one)(rids, steps, logits)


class Engine:
    def __init__(self, model, params, *, slots: int = 4, max_len: int = 256,
                 temperature: float = 0.0, seed: int = 0, compiled=None):
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.temperature = temperature
        self.cache = init_cache(model, slots, max_len)
        self.live: list[Optional[Request]] = [None] * slots
        self.lens = np.zeros(slots, np.int32)
        self.queue: list[Request] = []
        # poisoned slots: masked out of admission, populated by the
        # supervision layer (serve/engine_fault.py); the base engine
        # never adds to it
        self.dead_slots: set[int] = set()
        self.base_key = jax.random.PRNGKey(seed)
        # per-leaf index of the SLOT axis, read from the cache schema's
        # named axes ("batch") — `_merge_slots` must not guess it from
        # shapes: a stacked-layer leaf is (layers, slots, ...) and with
        # n_layers == slots a shape probe picks the layer axis and merges
        # the wrong rows (zeroing live layers for partially-admitted
        # batches — placement-dependent logits)
        axis_tree = jax.tree.map(
            lambda p: p.axes.index("batch"),
            model.cache_schema(slots, max_len),
            is_leaf=lambda x: hasattr(x, "axes"))
        self._slot_axes = tuple(jax.tree.flatten(axis_tree)[0])
        # `compiled` shares one jitted (prefill, decode) pair across many
        # engine instances over the same model (tests/benches rebuild
        # engines per scenario; a fresh jax.jit wrapper per instance
        # would recompile every time) — see `compile_model`
        self._prefill, self._decode = (compiled if compiled is not None
                                       else self.compile_model(model))

    @staticmethod
    def compile_model(model):
        """One jitted (prefill, decode) pair, shareable across engines
        via ``Engine(..., compiled=...)``."""
        return jax.jit(model.prefill), jax.jit(model.decode)

    def add_request(self, req: Request):
        """Enqueue one request for admission (the canonical entry point;
        `serve/frontend.py:ServeFrontend.submit` routes LM work here).
        Raises the typed `PromptTooLong` for a prompt the cache cannot
        hold."""
        if len(req.prompt) > self.max_len:
            raise PromptTooLong(req.rid, len(req.prompt), self.max_len)
        self.queue.append(req)

    def submit(self, req: Request, **kwargs):
        """Deprecated alias of `add_request` — the session API is
        `serve/frontend.py:ServeFrontend.submit`, which fronts both
        traffic classes behind one queue. Thin shim; dispatches through
        ``self.add_request`` so subclass overrides (TTL-aware admission,
        page-bounded admission) apply."""
        warnings.warn(
            "Engine.submit is deprecated; use ServeFrontend.submit "
            "(unified admission) or Engine.add_request",
            DeprecationWarning, stacklevel=2)
        return self.add_request(req, **kwargs)

    def _length_bucket(self, n: int) -> int:
        """Pad prompt lengths up to the next power of two so bursty mixed-
        length traffic funnels into a handful of prefill trace shapes —
        capped at max_len: the cache has no rows past it, and a valid
        prompt of length <= max_len must not be padded beyond it."""
        return min(1 << max(n - 1, 0).bit_length(), self.max_len)

    def _admissible(self, s: int) -> bool:
        """Is slot ``s`` a legal admission target? Free AND not poisoned
        (the supervision layer masks faulty slots via ``dead_slots``)."""
        return self.live[s] is None and s not in self.dead_slots

    def _pad_ok(self) -> bool:
        """Is right-padding a prompt safe for this model's cache?

        Safe for LINEAR causal-attention caches (pad positions only
        write K/V beyond the prompt, which decode masks via cache_len
        and overwrites before it becomes visible), but NOT for recurrent
        state (every consumed token mutates it) nor for sliding-window
        RING caches (the kept k[-W:] tail and the slot rotation are
        computed from the padded length, so pad keys evict real prompt
        keys) — those bucket by exact length instead."""
        cfg = self.model.cfg
        return (getattr(cfg, "ssm", None) is None and
                getattr(cfg, "sliding_window", None) is None)

    def _work_pending(self) -> bool:
        """Unfinished work anywhere in the engine (queued or live; the
        paged engine adds its admitted-but-laneless set)."""
        return bool(self.queue) or any(r is not None for r in self.live)

    def _pending_rids(self) -> set:
        return ({r.rid for r in self.queue} |
                {r.rid for r in self.live if r is not None})

    def _pre_dispatch_prefill(self, admitted: list) -> list:
        """Hook called with the claimed ``(slot, request)`` pairs before
        any prefill dispatch; returns the pairs that actually prefill.
        The supervision layer injects prefill faults here."""
        return admitted

    def _prefill_dispatch(self, batch):
        """One prefill dispatch — the supervision layer wraps this in
        transient-fault retry."""
        return self._prefill(self.params, batch, self.cache)

    def _admit(self):
        # claim every free slot first, then admit them in as few prefill
        # dispatches as possible (one per prompt-length bucket) — under
        # bursty load the seed's request-at-a-time admission paid one
        # dispatch per request. A REPLAYED request (evicted from a faulty
        # slot) prefills its prompt + already-generated prefix in one
        # dispatch; for a fresh request `out` is empty and the sequence
        # is just the prompt.
        admitted = []
        for s in range(self.slots):
            if self._admissible(s) and self.queue:
                req = self.queue.pop(0)
                self.live[s] = req
                admitted.append((s, req))
        if not admitted:
            return
        admitted = self._pre_dispatch_prefill(admitted)
        if not admitted:
            return
        if getattr(self.model.cfg, "is_encdec", False):
            # enc-dec decoders have no engine-supplied encoder frames:
            # prefill mode would run _encode, so keep the token-at-a-time
            # decode-mode admission for them
            for s, req in admitted:
                seq = req.prompt + req.out
                for t, tok in enumerate(seq):
                    batch = {"tokens": jnp.full((self.slots, 1), tok,
                                                jnp.int32),
                             "cache_len": jnp.asarray(t, jnp.int32)}
                    _, cache = self._decode(self.params, batch, self.cache)
                    self.cache = self._merge_slots(cache, [s])
                self.lens[s] = len(seq)
            return
        pad_ok = self._pad_ok()
        buckets: dict[int, list] = {}
        for s, req in admitted:
            n = len(req.prompt) + len(req.out)
            buckets.setdefault(self._length_bucket(n) if pad_ok else n,
                               []).append((s, req))
        for width, group in sorted(buckets.items()):
            # one padded prefill for the whole bucket: every admitted
            # slot's prompt K/V written in a single dispatch; the cache
            # merge keeps only the group's rows (identical semantics to
            # per-request admission, len(group)x fewer dispatches)
            tokens = np.zeros((self.slots, width), np.int32)
            for s, req in group:
                seq = req.prompt + req.out
                tokens[s, : len(seq)] = seq
            _, cache = self._prefill_dispatch(
                {"tokens": jnp.asarray(tokens)})
            self.cache = self._merge_slots(cache, [s for s, _ in group])
            for s, req in group:
                self.lens[s] = len(req.prompt) + len(req.out)

    def _merge_slots(self, new_cache, slots: list):
        # admission updates every slot's cache row; keep only the admitted
        # `slots` rows from the new cache. The slot axis per leaf comes
        # from the cache schema's named "batch" axis (`self._slot_axes`),
        # never from shape probing — see __init__ and
        # `_merge_cache_leaves` for why axis and mask work the way they do.
        mask = np.zeros(self.slots, bool)
        mask[np.asarray(slots)] = True
        old_leaves, treedef = jax.tree.flatten(self.cache)
        new_leaves = jax.tree.flatten(new_cache)[0]
        merged = _merge_cache_leaves(old_leaves, new_leaves,
                                     jnp.asarray(mask), self._slot_axes)
        return jax.tree.unflatten(treedef, merged)

    def _decode_dispatch(self, batch):
        """One batched decode dispatch for all slots — the supervision
        layer injects per-slot decode faults and transient retry here."""
        return self._decode(self.params, batch, self.cache)

    def _slot_retires(self, s: int) -> bool:
        """Does slot ``s`` retire its sampled token this step? The
        supervision layer masks hung slots (no retire, no heartbeat)."""
        return True

    def _on_retire(self, s: int, req: Request) -> None:
        """Hook after slot ``s`` retires one token (heartbeat source)."""

    def _on_finish(self, s: int, req: Request) -> None:
        """Hook after ``req`` completes and frees slot ``s``."""

    def _on_evict(self, req: Request) -> None:
        """Hook when the supervision layer evicts ``req`` from a faulty
        slot, BEFORE it requeues (the paged engine frees its pages here
        so the replay re-admits against fresh ones)."""

    def step(self):
        """One decode step for all live slots; returns finished requests."""
        self._admit()
        live_mask = np.array([r is not None for r in self.live])
        if not live_mask.any():
            return []
        last_tokens = np.zeros((self.slots, 1), np.int32)
        rids = np.zeros(self.slots, np.int32)
        steps = np.zeros(self.slots, np.int32)
        for s, r in enumerate(self.live):
            if r is not None:
                seq = r.prompt + r.out
                last_tokens[s, 0] = seq[-1]
                rids[s] = r.rid
                steps[s] = len(r.out)
        # per-slot positions (continuous batching): slot s's last token sits
        # at index lens[s]-1; dead slots park at 0 (overwritten on admit)
        cl = np.maximum(self.lens - 1, 0).astype(np.int32)
        batch = {"tokens": jnp.asarray(last_tokens),
                 "cache_len": jnp.asarray(cl)}
        logits, self.cache = self._decode_dispatch(batch)
        # one batched sample over ALL slots (dead slots ride along and
        # are ignored below), each slot on its request's OWN key stream —
        # see `_sample_per_request` for why this is the replay enabler
        if self.temperature > 0:
            sampled = np.asarray(_sample_per_request(
                self.base_key, jnp.asarray(rids), jnp.asarray(steps),
                logits[:, 0, :] / self.temperature))
        else:
            sampled = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
        finished = []
        for s, r in enumerate(self.live):
            if r is None or not self._slot_retires(s):
                continue
            tok = int(sampled[s])
            r.out.append(tok)
            self.lens[s] += 1
            self._on_retire(s, r)
            if len(r.out) >= r.max_new or self.lens[s] >= self.max_len - 1:
                r.done = True
                finished.append(r)
                self.live[s] = None
                self.lens[s] = 0
                self._on_finish(s, r)
        return finished

    def run_to_completion(self, max_steps: int = 10_000):
        """Step until every submitted request finishes; the finished
        requests are returned. Exhausting ``max_steps`` with work still
        queued/live raises the typed `EngineStalled` (carrying the
        unfinished rids and the done subset) instead of silently
        returning only what happened to finish."""
        done = []
        for _ in range(max_steps):
            done += self.step()
            if not self._work_pending():
                return done
        if not self._work_pending():
            return done
        raise EngineStalled(sorted(self._pending_rids()), done=done)


class PagedEngine(Engine):
    """`Engine` with a paged KV cache: ADMISSION IS BOUNDED BY FREE
    PAGES, not by ``slots``.

    The dense engine's per-slot caches reserve ``max_len`` rows per slot
    whether a request uses them or not, and the slot count doubles as
    the admission bound. Here every request's K/V lives in fixed-size
    pages of one preallocated pool (`serve/paged.py`: the SPM-bank
    analogue — one physical memory, time-shared through a block table),
    so:

    * ``slots`` becomes just the DECODE LANE count (the batch width of
      one decode dispatch). Admission pulls from the queue while the
      free-page count covers a request's worst-case footprint
      (``ceil(min(len + max_new, max_len) / page_size)`` pages, the max
      over cache leaves — a ring leaf never needs more than its W
      slots). Admitted requests beyond the lane count wait PREFILLED in
      ``paused``; when a lane frees, the refill is a block-table row
      swap — no prefill, no cache copy. With the default pool size
      (the dense engine's exact memory: ``slots * ceil(max_len /
      page_size)`` pages) short requests oversubscribe the lanes —
      ``peak_admitted`` > ``slots`` — which is the whole point.
    * prefill and decode read/write THROUGH the block table
      (`serve.paged.paged_prefill` / `paged_decode`, one fused dispatch
      each — same dispatch count as dense); the dense `_merge_slots`
      masked merge collapses into page assignment.
    * decode attends over each lane's ALLOCATED span instead of
      ``max_len`` — the paged compute saving the ``--check-paged``
      bench gate holds (`docs/BENCHMARKS.md`) — and masked positions
      contribute exactly zero, so output is BIT-identical to the dense
      path for greedy and temperature sampling alike
      (`tests/test_paged.py`).

    Models whose cache cannot be paged (recurrent state, enc-dec) raise
    the typed `PagedCacheUnsupported` at construction; a request whose
    footprint exceeds the POOL raises `InsufficientPages` at admission.
    The supervision layer stacks on top unchanged
    (`serve/engine_fault.py:FaultTolerantPagedEngine`)."""

    def __init__(self, model, params, *, slots: int = 4, max_len: int = 256,
                 temperature: float = 0.0, seed: int = 0, compiled=None,
                 page_size: int = 16, n_pages: Optional[int] = None):
        from repro.serve import paged as paged_mod
        self._paged = paged_mod
        if n_pages is None:
            # the dense engine's exact K/V memory, repartitioned into
            # pages (+1 for scratch): oversubscription comes from
            # requests shorter than max_len, not from extra memory
            n_pages = slots * (-(-max_len // page_size)) + 1
        self.pool = paged_mod.PagePool(model, page_size=page_size,
                                       n_pages=n_pages, max_len=max_len)
        self.table = paged_mod.PageTable(self.pool)
        super().__init__(model, params, slots=slots, max_len=max_len,
                         temperature=temperature, seed=seed,
                         compiled=compiled)
        self.cache = None        # every K/V row lives in the pool
        # admitted (pages held, prefilled) but waiting for a free lane
        self.paused: list[Request] = []
        self.peak_admitted = 0   # max concurrent admissions observed

    # ------------------------------------------------------- admission

    def _pages_for(self, req: Request) -> int:
        total = min(len(req.prompt) + len(req.out) + req.max_new,
                    self.max_len)
        return self.pool.pages_for(total)

    def add_request(self, req: Request):
        """Page-aware admission bound: a request whose worst-case
        footprint can NEVER fit the pool is rejected with the typed
        `InsufficientPages` (the paged twin of `PromptTooLong`); one
        that merely exceeds the current free count waits in the queue
        for pages to free."""
        need = self._pages_for(req)
        if need > self.pool.capacity:
            raise InsufficientPages(need, self.pool.n_free,
                                    self.pool.capacity)
        super().add_request(req)

    def _work_pending(self) -> bool:
        return bool(self.paused) or super()._work_pending()

    def _pending_rids(self) -> set:
        return super()._pending_rids() | {r.rid for r in self.paused}

    def _admit(self):
        # 1. refill free lanes from the paused set first: their K/V is
        # already paged in, so the "prefill" is a block-table row swap
        for s in range(self.slots):
            if not self.paused:
                break
            if self._admissible(s):
                req = self.paused.pop(0)
                self.live[s] = req
                self.lens[s] = len(req.prompt) + len(req.out)
        # 2. admit from the queue while free pages cover the head
        # request's footprint — THE admission bound; lanes don't gate it
        admitted: list[Request] = []
        while self.queue:
            need = self._pages_for(self.queue[0])
            if need > self.pool.n_free:
                break
            req = self.queue.pop(0)
            self.table.assign(req.rid, need)
            admitted.append(req)
        n_live = sum(r is not None for r in self.live)
        self.peak_admitted = max(
            self.peak_admitted, n_live + len(self.paused) + len(admitted))
        if not admitted:
            return
        # 3. claim free lanes for as many as fit; the rest decode later
        lane_pairs, pausing = [], []
        for req in admitted:
            s = next((s for s in range(self.slots)
                      if self._admissible(s)), None)
            if s is None:
                pausing.append(req)
            else:
                self.live[s] = req
                self.lens[s] = len(req.prompt) + len(req.out)
                lane_pairs.append((s, req))
        # the supervision hook probes LANE claims (a paused admission has
        # no slot identity yet; it is probed when it joins a lane's
        # decode dispatches)
        kept = self._pre_dispatch_prefill(lane_pairs)
        jobs = kept + [(None, r) for r in pausing]
        if not jobs:
            return
        # 4. prefill into pages, bucketed exactly like the dense engine
        pad_ok = self._pad_ok()
        buckets: dict[int, list] = {}
        for s, req in jobs:
            n = len(req.prompt) + len(req.out)
            buckets.setdefault(self._length_bucket(n) if pad_ok else n,
                               []).append((s, req))
        ps = self.pool.page_size
        for width, group in sorted(buckets.items()):
            qbt = self._paged.prefill_table_width(self.pool.specs, ps,
                                                  width)
            for i0 in range(0, len(group), self.slots):
                chunk = group[i0:i0 + self.slots]
                tokens = np.zeros((self.slots, width), np.int32)
                for row, (s, req) in enumerate(chunk):
                    seq = req.prompt + req.out
                    tokens[row, :len(seq)] = seq
                bt = self.table.block_table(
                    [req.rid for _, req in chunk] +
                    [None] * (self.slots - len(chunk)), width=qbt)
                self._prefill_dispatch(
                    {"tokens": jnp.asarray(tokens),
                     "block_table": jnp.asarray(bt)})
        self.paused.extend(pausing)

    # ------------------------------------------------------- dispatch

    def _prefill_dispatch(self, batch):
        logits, new_pools = self._paged.paged_prefill(
            self.model.prefill, self.pool.treedef, self.pool.specs,
            self.params, {"tokens": batch["tokens"]},
            tuple(self.pool.leaves), batch["block_table"])
        self.pool.leaves = list(new_pools)
        return logits, None

    def _decode_dispatch(self, batch):
        bt = self.table.block_table(
            [r.rid if r is not None else None for r in self.live])
        logits, new_pools = self._paged.paged_decode(
            self.model.decode, self.pool.treedef, self.pool.specs,
            self.params, batch, tuple(self.pool.leaves), jnp.asarray(bt))
        self.pool.leaves = list(new_pools)
        return logits, None

    # ------------------------------------------------------- lifecycle

    def _on_finish(self, s: int, req: Request) -> None:
        self.table.release(req.rid)
        super()._on_finish(s, req)

    def _on_evict(self, req: Request) -> None:
        # the replay re-admits against FRESH pages; stale ones free now
        if self.table.holds(req.rid):
            self.table.release(req.rid)
        super()._on_evict(req)

    def defrag(self) -> dict[int, int]:
        """Compact allocated pages onto the lowest ids (see
        `serve.paged.PageTable.defrag`); safe mid-decode — the
        continuation is bit-identical."""
        return self.table.defrag()


class ColumnScheduler:
    """Admission placement of independent biosignal streams onto column
    replicas (devices) — LOAD-AWARE when given telemetry.

    Two ways to use D columns: one heavy stream `shard_map`s each dispatch
    across all of them (`StreamConfig.n_columns=D`), or D independent
    streams each stay resident on ONE column — no cross-device halo, and
    per-column autotune winners stay valid because every column sees the
    single-column shape. This scheduler implements the second: `admit`
    pins a new stream to the least-loaded column, `release` frees it on
    stream close.

    "Least-loaded" is MEASURED when a `serve.stream.StreamTelemetry` is
    attached and warm: a column's load is the sum of its streams' EWMA
    windows/s, so a heavy sensor counts for what it actually consumes and
    a cheap one barely counts — balancing by live-stream count only when
    telemetry is cold (no inter-retire gap observed yet). Ties break by
    stream count then column index, so an idle machine still fills
    round-robin (the archsim pass deal).

    `rebalance` is the work-stealing step: when the max/min column-load
    ratio exceeds ``rebalance_ratio`` it re-pins streams from the most-
    to the least-loaded column (largest mover first, only while a move
    strictly shrinks the spread) and returns the
    ``{stream_id: new_device}`` moves for the caller to apply via
    `BiosignalStream.repin`. `deal_weights` is the sharded-stream
    complement: measured per-column throughput rates as a
    `column_shares` weight vector (`StreamConfig.column_weights`), so a
    column sharing its device with another tenant is dealt fewer frames.

    RETIRE-COUNT TRIGGER: pass ``rebalance_every=N`` (windows) and the
    scheduler subscribes to its telemetry's retire feed
    (`StreamTelemetry.add_retire_listener`) — `rebalance` then runs BY
    ITSELF once N windows have retired fleet-wide since the last pass,
    instead of a host-side poller calling it on a timer. The trigger
    consumes whatever the telemetry sees: per-batch retires from the
    host-driven path or one retire per upload from the per-upload loop
    (`serve.stream.BiosignalStream.process`). Triggered moves queue
    in ``pending_moves``; drain them with `pop_moves` and apply via
    `BiosignalStream.repin`. See `docs/ARCHITECTURE.md`
    (serving-runtime control loop).

    SUPERVISION (the fault-tolerant layer): pass ``heartbeat_timeout``
    (seconds) and/or a ``straggler`` (`runtime.fault.StragglerDetector`)
    and the scheduler watches column LIVENESS through the same retire
    feed — every retire from a placed stream beats the column's
    `runtime.fault.HeartbeatMonitor`, per-dispatch wall times go in via
    `record_batch_time`, and
    `supervise` declares a column dead on heartbeat timeout or straggler
    eviction, draining its streams onto survivors (`mark_dead`) and
    zeroing it out of `deal_weights`. The last column dying raises the
    typed `runtime.fault.InsufficientHealthyWorkers`. The requeue of a
    dead column's unretired frame ranges is the serving front-end's job
    (`serve/fault.py`); see `docs/ARCHITECTURE.md` (fault-tolerance
    closed loop).

    >>> sched = ColumnScheduler(telemetry=StreamTelemetry(),
    ...                         rebalance_every=256)
    >>> stream = BiosignalStream(app, cfg, device=sched.admit("sensor-7"))
    >>> ...  # retires accumulate; sched.pop_moves() hands back any re-pins
    """

    def __init__(self, devices=None, *, telemetry=None,
                 rebalance_ratio: float = 2.0,
                 rebalance_every: int | None = None,
                 heartbeat_timeout: float | None = None,
                 straggler: StragglerDetector | None = None,
                 clock=time.monotonic):
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        assert self.devices, "no devices to schedule columns on"
        assert rebalance_ratio >= 1.0, rebalance_ratio
        assert rebalance_every is None or rebalance_every >= 1
        self.telemetry = telemetry
        self.rebalance_ratio = rebalance_ratio
        self.rebalance_every = rebalance_every
        self.pending_moves: dict = {}
        # the device copies of graph tables its streams share (one per
        # graph, app and column; `serve/stream.py:StagedTables`)
        from repro.serve.stream import StagedTables

        self.tables = StagedTables()
        self._retired_since_rebalance = 0
        self._load = [0] * len(self.devices)
        self._placement: dict = {}
        # SUPERVISION state: the retire feed doubles as the heartbeat
        # source (a column that retires work is alive — per-batch and
        # per-upload retires both count), per-column batch
        # times feed the straggler detector, and `supervise` turns both
        # into dead-column declarations + stream drains.
        self._clock = clock
        self.dead: set[int] = set()
        self.withdrawn: set[int] = set()   # drained for re-provisioning
        self.heartbeats = (HeartbeatMonitor(timeout_s=heartbeat_timeout)
                           if heartbeat_timeout is not None else None)
        self.straggler = straggler
        if self.heartbeats is not None:
            assert telemetry is not None, \
                "heartbeat supervision needs a telemetry retire feed"
            now = clock()
            for c in range(len(self.devices)):   # grace period from t0
                self.heartbeats.beat(c, now)
            telemetry.add_retire_listener(self._beat_on_retire)
        if rebalance_every is not None:
            assert telemetry is not None, \
                "the retire-count trigger needs a telemetry retire feed"
            telemetry.add_retire_listener(self._on_retire)

    @property
    def n_columns(self) -> int:
        return len(self.devices)

    def healthy_columns(self) -> list[int]:
        """Columns not declared dead — the only legal placement targets."""
        return [c for c in range(len(self.devices)) if c not in self.dead]

    def column_of(self, stream_id) -> int:
        return self._placement[stream_id]

    def loads(self) -> list:
        """Live-stream count per column (admission balance introspection)."""
        return list(self._load)

    def _warm(self) -> bool:
        return self.telemetry is not None and self.telemetry.warm

    def _stream_weights(self) -> dict:
        """Every placed stream's load contribution: its measured EWMA rate
        when warm. A cold (not-yet-measured) stream counts the MEAN
        warm-stream rate — the same unmeasured-is-not-zero substitution
        as `deal_weights`; a unitless placeholder against windows/s loads
        would make a burst of cold admissions nearly invisible and pile
        them onto one column. Computed in one pass (the mean once, not
        per stream)."""
        rates = {s: (self.telemetry.stream_rate(s) if self.telemetry
                     else 0.0) for s in self._placement}
        warm = [r for r in rates.values() if r > 0.0]
        mean = sum(warm) / len(warm) if warm else 1.0
        return {s: (r if r > 0.0 else mean) for s, r in rates.items()}

    def measured_loads(self) -> list[float] | None:
        """Measured windows/s demand per column (sum of the column's
        streams' EWMA rates, cold streams counted at the mean warm rate),
        or None while telemetry is cold — callers then balance by stream
        count."""
        if not self._warm():
            return None
        loads = [0.0] * len(self.devices)
        for sid, w in self._stream_weights().items():
            loads[self._placement[sid]] += w
        return loads

    def admit(self, stream_id):
        """Place a new stream; returns the device to pin it to
        (`BiosignalStream(..., device=...)`). Rate-based (least measured
        load) when telemetry is warm, least-stream-count otherwise. Dead
        columns are never placement targets; with every column dead the
        fleet cannot admit — the typed `InsufficientHealthyWorkers`."""
        assert stream_id not in self._placement, \
            f"stream {stream_id!r} already placed"
        healthy = self.healthy_columns()
        if not healthy:
            raise InsufficientHealthyWorkers(
                "every column is dead; nothing to admit onto")
        measured = self.measured_loads()
        if measured is None:
            col = min(healthy, key=lambda i: (self._load[i], i))
        else:
            col = min(healthy,
                      key=lambda i: (measured[i], self._load[i], i))
        self._load[col] += 1
        self._placement[stream_id] = col
        if self.telemetry is not None:
            self.telemetry.attach(stream_id, col)
        return self.devices[col]

    def release(self, stream_id) -> None:
        self._load[self._placement.pop(stream_id)] -= 1
        if self.telemetry is not None:
            self.telemetry.detach(stream_id)

    def _move(self, stream_id, col: int) -> None:
        old = self._placement[stream_id]
        self._load[old] -= 1
        self._load[col] += 1
        self._placement[stream_id] = col
        if self.telemetry is not None:
            self.telemetry.attach(stream_id, col)

    def _on_retire(self, stream_id, n_windows: int) -> None:
        """Telemetry retire listener: accumulate retired windows and run
        the work-stealing pass once ``rebalance_every`` of them landed —
        the retire-count trigger that replaces a host-side poller. Only
        streams this scheduler placed count toward the trigger (a foreign
        stream sharing the telemetry is not this scheduler's load)."""
        if stream_id not in self._placement:
            return
        self._retired_since_rebalance += n_windows
        if self._retired_since_rebalance >= self.rebalance_every:
            self._retired_since_rebalance = 0
            self.pending_moves.update(self.rebalance())

    def pop_moves(self) -> dict:
        """Drain the retire-triggered re-pins: {stream_id: new device},
        empty when the trigger hasn't fired (or found nothing to move).
        Callers apply each with `BiosignalStream.repin`."""
        moves, self.pending_moves = self.pending_moves, {}
        return moves

    def rebalance(self) -> dict:
        """One work-stealing pass. While the max/min column-load ratio
        exceeds ``rebalance_ratio`` (a zero-load column under a loaded one
        counts as exceeded), move the heaviest stream that strictly
        shrinks the max-min spread from the most- to the least-loaded
        column. Returns {stream_id: new device}; apply with
        `BiosignalStream.repin`."""
        moves: dict = {}
        healthy = self.healthy_columns()
        if len(healthy) < 2:
            return moves
        for _ in range(len(self._placement) or 1):
            loads = self.measured_loads()
            if loads is None:
                loads = [float(c) for c in self._load]
            hi = max(healthy, key=lambda i: (loads[i], -i))
            lo = min(healthy, key=lambda i: (loads[i], i))
            if loads[hi] <= 0.0 or \
                    (loads[lo] > 0.0 and
                     loads[hi] / loads[lo] <= self.rebalance_ratio):
                break
            weights = self._stream_weights()
            movers = sorted(
                (s for s, c in self._placement.items() if c == hi),
                key=weights.__getitem__, reverse=True)
            pick = next((s for s in movers
                         if loads[lo] + weights[s] < loads[hi]), None)
            if pick is None:        # no move shrinks the spread
                break
            self._move(pick, lo)
            moves[pick] = self.devices[lo]
        return moves

    # ------------------------------------------------------- supervision

    def _beat_on_retire(self, stream_id, n_windows: int) -> None:
        """Telemetry retire listener: a retire from one of THIS
        scheduler's streams is a heartbeat for its column — per-batch
        retires (`serve.stream.BiosignalStream._collect`) and per-upload
        retires (`serve.stream.BiosignalStream._process_upload`) both
        land here."""
        if stream_id in self._placement:
            self.heartbeats.beat(self._placement[stream_id], self._clock())

    def record_batch_time(self, column: int, seconds: float) -> None:
        """Feed one column dispatch's wall time to the straggler
        detector (the serving analogue of a training step time)."""
        if self.straggler is not None and column not in self.dead:
            self.straggler.record(column, seconds)

    def mark_dead(self, column: int) -> dict:
        """Declare a column dead and DRAIN it: every stream pinned to it
        re-pins onto the least-loaded surviving column (the drain moves
        land in ``pending_moves`` like triggered rebalances — apply with
        `BiosignalStream.repin`). The column stops being a placement /
        rebalance / heartbeat target and its measured rate is zeroed out
        of future `deal_weights`. Raises `InsufficientHealthyWorkers`
        when the last column dies — the caller decides whether that is
        an outage or a wait-for-capacity."""
        if column in self.dead:
            return {}
        self.dead.add(column)
        if self.heartbeats is not None:
            self.heartbeats.forget(column)
        if self.straggler is not None:
            self.straggler.forget(column)
        healthy = self.healthy_columns()
        if not healthy:
            raise InsufficientHealthyWorkers(
                f"column {column} was the last healthy column")
        moves: dict = {}
        for sid, c in sorted(self._placement.items(), key=lambda kv: kv[0]):
            if c != column:
                continue
            measured = self.measured_loads()
            target = min(healthy,
                         key=(lambda i: (self._load[i], i)) if measured
                         is None else (lambda i: (measured[i],
                                                  self._load[i], i)))
            self._move(sid, target)
            moves[sid] = self.devices[target]
        self.pending_moves.update(moves)
        return moves

    def supervise(self, now: float | None = None) -> list[int]:
        """One supervision pass: declare dead every column whose
        heartbeat timed out (no retire for ``heartbeat_timeout``
        seconds) or that the straggler detector evicted (persistently
        slower than `StragglerDetector.straggler_factor` x the fleet
        median), drain each via `mark_dead`, and return the newly-dead
        columns. The closed loop is detection -> drain -> requeue ->
        re-deal; this method is the detection + drain half — the requeue
        half (unretired frame ranges onto survivors) lives in
        `serve/fault.py`, see `docs/ARCHITECTURE.md`."""
        suspects: list[int] = []
        if self.heartbeats is not None:
            suspects += self.heartbeats.dead(
                self._clock() if now is None else now)
        if self.straggler is not None:
            suspects += self.straggler.stragglers()
        newly = []
        for c in suspects:
            if 0 <= c < len(self.devices) and c not in self.dead:
                newly.append(c)
                self.mark_dead(c)
        return newly

    def deal_weights(self, band: float = 0.0) -> tuple | None:
        """Measured per-column throughput rates (the retire-rate EWMAs) as
        a weight vector for the non-uniform deal
        (`StreamConfig.column_weights` / `column_shares`), or None while
        telemetry is cold. A column that never retired anything gets the
        mean observed rate — unobserved is not the same as broken.

        ``band`` is the deal's deadband (same thrash-guard idea as
        ``rebalance_ratio``): columns whose measured rates differ by less
        than ``band`` (relative, walked over the rate-sorted columns) are
        considered EQUALLY capable and share their cluster's mean rate —
        EWMA jitter between identical columns must not deal them unequal
        shares; only a genuine rate gap wider than the band changes the
        deal. 0 disables it.

        DEAD columns are zeroed: a drained column's weight is exactly
        0.0 (never the stale pre-death EWMA, never the mean), so the
        degraded deal rides `column_shares`' zero-weight path and deals
        it nothing. All columns dead raises
        `InsufficientHealthyWorkers` — there is no deal to compute."""
        if self.telemetry is None:
            return None
        healthy = self.healthy_columns()
        if not healthy:
            raise InsufficientHealthyWorkers(
                "every column is dead; no deal weights to compute")
        rates = [self.telemetry.column_rate(c)
                 for c in range(len(self.devices))]
        seen = [rates[c] for c in healthy if rates[c] > 0.0]
        if not seen:
            return None
        mean = sum(seen) / len(seen)
        rates = [r if r > 0.0 else mean for r in rates]
        if band > 0.0:
            # cluster only the healthy columns: a dead column's stale
            # rate must not drag a cluster mean around
            order = sorted(healthy, key=lambda c: rates[c])
            clusters, cur = [], [order[0]]
            for c in order[1:]:
                if rates[c] <= rates[cur[0]] * (1.0 + band):
                    cur.append(c)       # within the band of the cluster
                else:                   # floor: same capability class
                    clusters.append(cur)
                    cur = [c]
            clusters.append(cur)
            for cl in clusters:
                m = sum(rates[c] for c in cl) / len(cl)
                for c in cl:
                    rates[c] = m
        for c in self.dead:
            rates[c] = 0.0
        return tuple(rates)

    # --------------------------------------------- class re-provisioning

    def withdraw(self, column: int):
        """Administratively DRAIN a column so its device can serve the
        other traffic class (the unified front-end lends columns to the
        LM engine under load — `serve/frontend.py:ServeFrontend`).
        Reuses the `mark_dead` drain machinery — streams re-pin onto
        survivors, the column leaves placement/heartbeat/deal targets —
        but records the column as WITHDRAWN, not failed, so `restore`
        can hand it back. Returns ``(device, moves)`` where ``moves`` is
        the `mark_dead`-style ``{stream_id: new_device}`` drain to apply
        via `BiosignalStream.repin`. Withdrawing the last healthy column
        raises `InsufficientHealthyWorkers` (the stream class keeps a
        quorum of one)."""
        if column in self.dead:
            raise ValueError(f"column {column} is already dead/withdrawn")
        if len(self.healthy_columns()) < 2:
            # checked BEFORE the drain so a refused withdraw leaves the
            # scheduler untouched (mark_dead declares first, then raises)
            raise InsufficientHealthyWorkers(
                f"column {column} is the last healthy column; "
                "cannot withdraw it for re-provisioning")
        moves = self.mark_dead(column)
        self.withdrawn.add(column)
        return self.devices[column], moves

    def restore(self, column: int) -> None:
        """Return a `withdraw`n column to the placement set: it becomes
        a placement/rebalance target again and its heartbeat restarts
        with a fresh grace period. Only withdrawn columns are
        restorable — a column that FAILED stays dead."""
        if column not in self.withdrawn:
            raise ValueError(f"column {column} was not withdrawn")
        self.withdrawn.discard(column)
        self.dead.discard(column)
        if self.heartbeats is not None:
            self.heartbeats.beat(column, self._clock())

    # ------------------------------------------------------ stream entry

    def place_stream(self, app=None, cfg=None, *, stream_id):
        """Admit + construct in one call: a `BiosignalStream` whose every
        dispatch is committed to the assigned column, reading the graph
        tables the scheduler keeps on that column (`tables`), and (when
        the scheduler carries telemetry) reports its retires to it. (The
        unified admission path — `serve/frontend.py:ServeFrontend.submit`
        with a `StreamOpen` — lands here.)"""
        from repro.serve.stream import BiosignalStream

        device = self.admit(stream_id)
        return BiosignalStream(app, cfg, device=device,
                               telemetry=self.telemetry,
                               stream_id=stream_id,
                               column=self._placement[stream_id],
                               tables=self.tables)

    def open_stream(self, app=None, cfg=None, *, stream_id):
        """Deprecated name for `place_stream` (kept as a shim for one
        release; the unified front-end made `submit` the public verb)."""
        warnings.warn(
            "ColumnScheduler.open_stream is deprecated; use "
            "ServeFrontend.submit (unified admission) or "
            "ColumnScheduler.place_stream",
            DeprecationWarning, stacklevel=2)
        return self.place_stream(app, cfg, stream_id=stream_id)
