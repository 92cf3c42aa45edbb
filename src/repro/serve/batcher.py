"""Slot-based continuous batching over the compiled decode step
(docs/serving.md).

The batcher owns one batched cache (``engine.batch_size`` slots) and a
fixed :class:`PagePool` of cache pages. Requests join mid-stream:
admission performs a batch-1 prefill through the legacy model API
(prefill/decode disaggregation), writes the prefilled cache into the
request's slot, and leases its cache pages; every step then runs ONE
compiled decode over all slots at their own positions (the decode
graph's ``pos`` activation is per-slot). Finished requests retire
immediately — their pages return to the pool exactly once and the slot
recycles to the next queued request — so the decode batch stays full
without ever re-padding or re-compiling.

Determinism: the step counter is the only clock, and sampling keys are
``fold_in(fold_in(seed, uid), pos)`` — a request's tokens depend only
on its own uid/positions, never on which neighbors share the batch.
Replaying the same arrival trace reproduces the same outputs.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation


class PagePoolError(RuntimeError):
    """Raised on page-accounting violations (double free, double lease,
    freeing an unknown uid) — these are serving bugs, never warnings."""


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival`` is the step index at which
    the request becomes visible to the batcher (synthetic traces)."""

    uid: int
    prompt: np.ndarray            # [S] int32 token ids
    max_new_tokens: int
    arrival: int = 0


@dataclasses.dataclass
class RequestResult:
    uid: int
    tokens: np.ndarray            # [max_new_tokens] int32
    submitted: int                # step the request arrived
    admitted: int                 # step a slot + pages were leased
    first_token: int              # step the prefill token was emitted
    finished: int                 # step the last token was emitted


class PagePool:
    """A fixed pool of cache pages with exact lease accounting.

    Serving-level admission control: a request leases
    ``ceil(cache_len / page_size)`` pages for its whole lifetime and
    returns them exactly once on retirement. Double leases and double
    frees raise :class:`PagePoolError` — the test suite's invariant.

    ``host_pages > 0`` enables the two-tier mode (repro.axe.hetero's
    host class, applied to serving): a live lease can be *evicted* to
    the host tier — its accelerator pages return to the pool while the
    uid keeps a host-tier lease of the same size — and later *leased
    back*. Page round trips are counted in ``transfer_pages`` (the
    byte-level movement is the batcher's Transfer, not the pool's)."""

    def __init__(self, n_pages: int, page_size: int, *, host_pages: int = 0):
        if n_pages <= 0 or page_size <= 0:
            raise ValueError("n_pages and page_size must be positive")
        if host_pages < 0:
            raise ValueError("host_pages must be non-negative")
        self.n_pages = n_pages
        self.page_size = page_size
        self.host_pages = host_pages
        self._free: List[int] = list(range(n_pages))
        self._leased: Dict[int, Tuple[int, ...]] = {}
        self._host: Dict[int, int] = {}       # uid -> n pages parked on host
        self.freed_count: Dict[int, int] = {}
        self.transfer_pages: Dict[str, int] = {"out": 0, "in": 0}

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def host_available(self) -> int:
        return self.host_pages - sum(self._host.values())

    def pages_for(self, cache_len: int) -> int:
        return -(-cache_len // self.page_size)

    def alloc(self, uid: int, n: int) -> Tuple[int, ...]:
        if uid in self._leased or uid in self._host:
            raise PagePoolError(f"uid {uid} already holds a lease")
        if n > len(self._free):
            raise PagePoolError(
                f"uid {uid} wants {n} pages, only {len(self._free)} free"
            )
        pages = tuple(self._free[:n])
        del self._free[:n]
        self._leased[uid] = pages
        return pages

    def evict(self, uid: int) -> int:
        """Move a live lease to the host tier: the accelerator pages
        return to the pool, the uid keeps a host lease of equal size."""
        pages = self._leased.get(uid)
        if pages is None:
            if uid in self._host:
                raise PagePoolError(f"uid {uid} is already evicted")
            raise PagePoolError(f"uid {uid} holds no lease to evict")
        if len(pages) > self.host_available:
            raise PagePoolError(
                f"uid {uid} wants {len(pages)} host pages, only "
                f"{self.host_available} of {self.host_pages} free"
            )
        del self._leased[uid]
        self._free.extend(pages)
        self._host[uid] = len(pages)
        self.transfer_pages["out"] += len(pages)
        return len(pages)

    def lease_back(self, uid: int) -> Tuple[int, ...]:
        """Return an evicted lease to the accelerator tier."""
        n = self._host.get(uid)
        if n is None:
            raise PagePoolError(f"uid {uid} holds no host lease")
        if n > len(self._free):
            raise PagePoolError(
                f"uid {uid} wants {n} pages back, only {len(self._free)} free"
            )
        pages = tuple(self._free[:n])
        del self._free[:n]
        del self._host[uid]
        self._leased[uid] = pages
        self.transfer_pages["in"] += n
        return pages

    def free(self, uid: int) -> None:
        pages = self._leased.pop(uid, None)
        if pages is None:
            if self._host.pop(uid, None) is not None:
                # finishing while parked releases the host lease
                self.freed_count[uid] = self.freed_count.get(uid, 0) + 1
                return
            raise PagePoolError(f"uid {uid} holds no lease (double free?)")
        self._free.extend(pages)
        self.freed_count[uid] = self.freed_count.get(uid, 0) + 1

    def leased_pages(self) -> Dict[int, Tuple[int, ...]]:
        return dict(self._leased)

    def host_leased(self) -> Dict[int, int]:
        return dict(self._host)


@dataclasses.dataclass
class _Slot:
    index: int
    uid: Optional[int] = None     # None: free
    pos: int = 0
    remaining: int = 0
    tokens: Optional[List[int]] = None
    last_tok: int = 0
    result: Optional[RequestResult] = None


@dataclasses.dataclass
class _Parked:
    """A preempted request living on the host tier: its saved decode
    state plus the host-resident copy of its cache slice."""

    uid: int
    pos: int
    remaining: int
    tokens: List[int]
    last_tok: int
    result: RequestResult
    cache: object                 # numpy cache slice [n_super, 1, ...]
    parked_at: int


class ContinuousBatcher:
    """Continuous batching driver over a :class:`ServeEngine`.

    ``engine.batch_size`` is the slot count; every decode step is one
    compiled-executable call over all slots (``engine.decode_step``).
    ``temperature``/``top_k`` follow the engine's sampling semantics
    (temperature 0 = greedy)."""

    def __init__(self, engine, *, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 offload: bool = False,
                 host_pages: Optional[int] = None):
        self.engine = engine
        self.n_slots = engine.batch_size
        per_slot = -(-engine.max_seq // page_size)
        if host_pages is None:
            host_pages = self.n_slots * per_slot if offload else 0
        self.pool = PagePool(
            n_pages if n_pages is not None else self.n_slots * per_slot,
            page_size,
            host_pages=host_pages,
        )
        self.offload = offload
        self.parked: List[_Parked] = []
        #: bytes moved across the host link by page-out/page-in, and the
        #: Transfer-tagged movement log the tests/dryrun assert on
        self.transfer_bytes = 0
        self.transfer_log: List[Tuple[str, int, str]] = []
        self.temperature = (
            engine.temperature if temperature is None else temperature
        )
        self.top_k = top_k
        self.slots = [_Slot(i) for i in range(self.n_slots)]
        self.queue: List[Request] = []
        self.pending: List[Request] = []   # not yet arrived (trace replay)
        self.step_count = 0
        self.results: Dict[int, RequestResult] = {}
        self._submit_step: Dict[int, int] = {}
        #: per run of the expert layer, newest last: (step, "prefill" or
        #: "decode", device array ``[layers, 2]``: ``models.moe.load`` per
        #: layer). Kept on the device, read by step index afterwards; the
        #: last ``EXPERT_LOAD_KEPT`` runs. Empty for a model without experts
        self.expert_load: Deque[Tuple[int, str, jax.Array]] = collections.deque(
            maxlen=self.EXPERT_LOAD_KEPT)
        self.cache = engine.api.cache_init(self.n_slots, engine.max_seq)
        if engine.mesh is not None:
            self.cache = engine._place_cache(self.cache)

    EXPERT_LOAD_KEPT = 4096

    def _log_expert_load(self, kind: str) -> None:
        load = self.engine.last_expert_load
        if load is not None:
            self.expert_load.append((self.step_count, kind, load))

    # -- request intake ---------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request; it becomes admissible at ``req.arrival``."""
        if req.uid in self._submit_step or req.uid in self.results:
            raise ValueError(f"duplicate uid {req.uid}")
        self._submit_step[req.uid] = max(req.arrival, self.step_count)
        self.pending.append(req)
        self.pending.sort(key=lambda r: (r.arrival, r.uid))

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s.uid is not None)

    def _free_slot(self) -> Optional[_Slot]:
        for s in self.slots:
            if s.uid is None:
                return s
        return None

    # -- slot lifecycle ---------------------------------------------------
    def _admit(self, req: Request, slot: _Slot) -> None:
        eng = self.engine
        prompt = np.asarray(req.prompt, np.int32)
        cache_len = min(len(prompt) + req.max_new_tokens, eng.max_seq)
        self.pool.alloc(req.uid, self.pool.pages_for(cache_len))

        # batch-1 prefill through the legacy model API (disaggregated
        # from the batched compiled decode)
        with TraceAnnotation("prefill", uid=req.uid, prompt_len=len(prompt)):
            one = eng.api.cache_init(1, eng.max_seq)
            logits, one = eng.prefill({"tokens": prompt[None, :]}, one)
            self._log_expert_load("prefill")
        with TraceAnnotation("first_token", uid=req.uid):
            tok = int(self._sample_one(req.uid, len(prompt) - 1, logits[0, -1]))

        # write the prefilled cache into this slot (leaves are
        # [n_super, B, ...]: batch is axis 1)
        with TraceAnnotation("slot_write", uid=req.uid):
            self.cache = jax.tree.map(
                lambda big, new: jax.lax.dynamic_update_slice_in_dim(
                    big, new.astype(big.dtype), slot.index, axis=1
                ),
                self.cache, one,
            )
        slot.uid = req.uid
        slot.pos = len(prompt)
        slot.remaining = req.max_new_tokens - 1
        slot.tokens = [tok]
        slot.last_tok = tok
        slot.result = RequestResult(
            uid=req.uid, tokens=np.zeros(0, np.int32),
            submitted=self._submit_step[req.uid],
            admitted=self.step_count, first_token=self.step_count,
            finished=-1,
        )
        if slot.remaining == 0:
            self._retire(slot)

    # -- host-tier preemption (two-tier PagePool) -------------------------
    def _cache_slice(self, index: int):
        """The one-slot cache slice, copied to host memory (the
        Transfer "slice": page-out of a leased cache)."""
        return jax.tree.map(
            lambda big: np.asarray(
                jax.lax.dynamic_slice_in_dim(big, index, 1, axis=1)
            ),
            self.cache,
        )

    def _park(self, slot: _Slot) -> None:
        """Preempt a live slot: evict its pages to the host tier, copy
        its cache slice to host memory, and save its decode state so a
        later lease-back resumes with identical tokens (sampling is
        uid/pos-keyed, so parking never changes a request's stream)."""
        sliced = self._cache_slice(slot.index)
        self.transfer_bytes += sum(a.nbytes for a in jax.tree.leaves(sliced))
        self.transfer_log.append(("page_out", slot.uid, "Transfer"))
        self.pool.evict(slot.uid)
        self.parked.append(_Parked(
            uid=slot.uid, pos=slot.pos, remaining=slot.remaining,
            tokens=slot.tokens, last_tok=slot.last_tok, result=slot.result,
            cache=sliced, parked_at=self.step_count,
        ))
        slot.uid = None
        slot.pos = 0
        slot.remaining = 0
        slot.tokens = None
        slot.last_tok = 0
        slot.result = None

    def _resume(self, parked: _Parked, slot: _Slot) -> None:
        """Lease an evicted request back onto the accelerator tier (the
        Transfer "gather": page-in of the host-resident slice)."""
        self.pool.lease_back(parked.uid)
        self.transfer_bytes += sum(
            a.nbytes for a in jax.tree.leaves(parked.cache)
        )
        self.transfer_log.append(("page_in", parked.uid, "Transfer"))
        self.cache = jax.tree.map(
            lambda big, new: jax.lax.dynamic_update_slice_in_dim(
                big, jnp.asarray(new).astype(big.dtype), slot.index, axis=1
            ),
            self.cache, parked.cache,
        )
        slot.uid = parked.uid
        slot.pos = parked.pos
        slot.remaining = parked.remaining
        slot.tokens = parked.tokens
        slot.last_tok = parked.last_tok
        slot.result = parked.result

    def _page_out_for(self, needed: int, protect: set) -> bool:
        """Evict live slots (largest remaining work first, uid as the
        deterministic tie-break) until ``needed`` accelerator pages are
        free. ``protect`` uids (resumed this tick) are never re-parked —
        that would thrash the host link without progress. Returns False
        when eviction cannot make room."""
        while self.pool.available < needed:
            live = [
                s for s in self.slots
                if s.uid is not None and s.uid not in protect
                and len(self.pool.leased_pages().get(s.uid, ())) <= self.pool.host_available
            ]
            if not live:
                return False
            victim = max(live, key=lambda s: (s.remaining, s.uid))
            self._park(victim)
        return True

    def _retire(self, slot: _Slot) -> None:
        self.pool.free(slot.uid)
        res = slot.result
        res.tokens = np.asarray(slot.tokens, np.int32)
        res.finished = self.step_count
        self.results[slot.uid] = res
        slot.uid = None
        slot.pos = 0
        slot.remaining = 0
        slot.tokens = None
        slot.last_tok = 0
        slot.result = None

    # -- sampling ---------------------------------------------------------
    def _keys(self, uids: np.ndarray, pos: np.ndarray):
        base = jax.random.PRNGKey(self.engine.rng_seed)
        return jax.vmap(
            lambda u, p: jax.random.fold_in(jax.random.fold_in(base, u), p)
        )(jnp.asarray(uids, jnp.uint32), jnp.asarray(pos, jnp.uint32))

    def _mask_top_k(self, logits):
        if self.top_k is not None and self.top_k > 0:
            kth = jax.lax.top_k(logits, self.top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return logits

    def _sample_one(self, uid: int, pos: int, logits) -> int:
        logits = self._mask_top_k(logits)
        if self.temperature <= 0.0:
            return int(jnp.argmax(logits, axis=-1))
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.engine.rng_seed), uid),
            np.uint32(pos),
        )
        return int(jax.random.categorical(key, logits / self.temperature))

    def _sample_batch(self, uids: np.ndarray, pos: np.ndarray, logits):
        logits = self._mask_top_k(logits)
        if self.temperature <= 0.0:
            return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        keys = self._keys(uids, pos)
        toks = jax.vmap(
            lambda k, lg: jax.random.categorical(k, lg / self.temperature)
        )(keys, logits)
        return np.asarray(toks, np.int32)

    # -- the serving loop -------------------------------------------------
    def step(self) -> bool:
        """One scheduler tick: admit arrivals into free slots, run one
        batched compiled decode over the active slots, retire finished
        requests. Returns False when nothing is left to do.

        The tick and its host phases are profiler spans
        (docs/serving.md, "Tracing a server"): ``step`` (args ``step``,
        ``live``, ``queued``) holds ``admit`` (arg ``uid``; children
        ``prefill``, ``first_token``, ``slot_write``), ``inputs``,
        ``decode`` and ``sample``. A span waits for nothing on the
        device; with no profiler running it records nothing."""
        with TraceAnnotation("step", step=self.step_count, live=self.active,
                             queued=len(self.queue) + len(self.pending)):
            return self._step()

    def _step(self) -> bool:
        # arrivals whose time has come
        while self.pending and self.pending[0].arrival <= self.step_count:
            self.queue.append(self.pending.pop(0))
        # lease parked requests back first (FIFO by park order): they
        # were admitted before anything still queued
        resumed: set = set()
        while self.parked:
            slot = self._free_slot()
            if slot is None:
                break
            need = self.pool.host_leased().get(self.parked[0].uid, 0)
            if need > self.pool.available:
                break
            p = self.parked.pop(0)
            self._resume(p, slot)
            resumed.add(p.uid)
        # admit while there is a slot AND pages for the whole request
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                break
            req = self.queue[0]
            cache_len = min(
                len(req.prompt) + req.max_new_tokens, self.engine.max_seq
            )
            need = self.pool.pages_for(cache_len)
            if need > self.pool.n_pages:
                raise PagePoolError(
                    f"uid {req.uid} needs {need} pages; the pool only has "
                    f"{self.pool.n_pages}"
                )
            if need > self.pool.available:
                # head-of-line waits for pages (deterministic order);
                # in offload mode, page cold requests out to the host
                # tier instead of stalling the line
                if not (self.offload and self._page_out_for(need, resumed)
                        and self._free_slot() is not None):
                    break
                slot = self._free_slot()
            self.queue.pop(0)
            with TraceAnnotation("admit", uid=req.uid):
                self._admit(req, slot)

        live = [s for s in self.slots if s.uid is not None]
        if not live:
            done = not (self.queue or self.pending or self.parked)
            self.step_count += 1
            return not done

        with TraceAnnotation("inputs"):
            tok = jnp.asarray([s.last_tok for s in self.slots], jnp.int32)
            pos = jnp.asarray([s.pos for s in self.slots], jnp.int32)
        with TraceAnnotation("decode"):
            logits, self.cache = self.engine.decode_step(tok, self.cache, pos)
            self._log_expert_load("decode")
        with TraceAnnotation("sample"):
            sampled = self._sample_batch(
                np.asarray([s.uid if s.uid is not None else 0 for s in self.slots]),
                np.asarray([s.pos for s in self.slots]),
                logits,
            )
        self.step_count += 1
        for s in live:
            t = int(sampled[s.index])
            s.tokens.append(t)
            s.last_tok = t
            s.pos += 1
            s.remaining -= 1
            if s.remaining <= 0:
                self._retire(s)
        return True

    def run(self, requests: Sequence[Request] = ()) -> Dict[int, RequestResult]:
        """Drive the loop to completion over ``requests`` (plus anything
        already submitted); returns results keyed by uid."""
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        return dict(self.results)
