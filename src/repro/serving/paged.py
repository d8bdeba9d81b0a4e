"""Paged-KV serving engine: block tables + chunked prefill + approx-draft
speculative decoding.

`PagedEngine` subclasses the whole-slot `Engine` and replaces only the
device-state layout and the step loop; admission validation, tier
ladders, deadlines, metering, and eviction accounting are inherited.
Three capabilities stack, each individually optional:

  1. **Paged KV** (always on): `max_len`-scaling cache leaves live in
     global page pools (`PagedArena`); a host-side `PageAllocator` hands
     out block tables with reserve-ahead allocation (every page a
     request can ever touch is reserved at admission, so decode can
     never deadlock mid-request), prefix sharing, and COW bookkeeping.
     The jitted steps gather a dense per-slot view that is bit-identical
     to the baseline arena at every valid position, so paged serving
     emits exactly the tokens the slot engine emits.
  2. **Chunked prefill** (`prefill_chunk=c`): prompts longer than `c`
     prefill in `c`-token chunks, at most `chunk_budget` chunks per
     tick, *interleaved* with decode — short requests no longer wait
     behind a long prompt's monolithic prefill (the TTFT win the
     benchmarks gate on).  The chunk step is `api.chunk_step`, a scan of
     the family's own `decode_step`, so partial-prefill state is exact
     for every family.
  3. **Speculative decoding** (`draft_tier=name`): an approximate
     multiplier tier (PR 8's ladder planes) drafts `spec_k` greedy
     tokens on a throwaway gathered view; the serving tier re-runs them
     in one verify scan and emits the longest agreeing prefix plus one
     correction.  Rejected positions are scattered to the trash page —
     they never enter the KV pools — and `Completion.spec` carries the
     proposed/accepted/corrections audit (`accepted + corrections ==
     len(tokens)` by construction).  Sampled (temperature > 0) rows
     bypass speculation — they emit one token per step from the same
     per-row RNG stream the baseline uses, so seeded sampling stays
     token-identical too.

Token-identity invariants the differential suite pins
(`tests/test_serving_paged.py`): masked attention lanes contribute
exactly 0 (−1e30 → exp underflow), so stale page garbage is invisible;
draft/verify/chunk are scans of the SAME `decode_step` the baseline
runs; greedy rows never consume RNG and sampled rows split once per
emitted token in both engines.
"""

from __future__ import annotations

import hashlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import api
from repro.serving import engine as eng
from repro.serving import sampling
from repro.serving.arena import PagedArena
from repro.serving.engine import Engine, _Slot
from repro.serving.paging import (
    PageAllocator, PageLease, PagingError, TRASH_PAGE)
from repro.serving.types import Request
from repro.sharding import ctx, rules


class _ChunkJob:
    """A request mid-chunked-prefill: holds the single-row workspace
    cache between ticks (its slot + pages are already reserved)."""

    def __init__(self, request: Request, slot_id: int, lease: PageLease,
                 digest: str, key: jax.Array, extras: dict,
                 workspace: dict, pos: int):
        self.request = request
        self.slot_id = slot_id
        self.lease = lease
        self.digest = digest
        self.key = key
        self.extras = extras
        self.workspace = workspace
        self.pos = pos


class PagedEngine(Engine):
    """Paged + chunked + speculative continuous-batching engine.

    Extra args on top of `Engine`:
      page_size: KV positions per page.
      n_pages: pool pages incl. the trash page; default sizes the pool
        so full occupancy at max_len always fits
        (capacity * ceil(max_len / page_size) + 1).
      prefill_chunk: chunk length for interleaved prefill; None/0 keeps
        the baseline's atomic prefill-then-join admission.
      chunk_budget: prefill chunks advanced per tick (oldest job first).
      draft_tier: multiplier-tier name drafting speculative tokens
        (e.g. "trunc4x4"; "exact" gives the 100%-acceptance identity
        draft).  None disables speculation.
      spec_k: draft tokens proposed per speculative step.
      prefix_cache: hash-matched prompt-prefix page sharing on/off.
    """

    def __init__(self, cfg: ModelConfig, params: Any | None = None, *,
                 page_size: int = 16, n_pages: int | None = None,
                 prefill_chunk: int | None = None, chunk_budget: int = 1,
                 draft_tier: str | None = None, spec_k: int = 4,
                 prefix_cache: bool = True, **kw):
        capacity = kw.get("capacity", 4)
        max_len = kw.get("max_len", 256)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1 (got {page_size})")
        if prefill_chunk is not None and prefill_chunk < 1:
            prefill_chunk = None
        self.page_size = page_size
        self.n_pages = (n_pages if n_pages is not None
                        else capacity * (-(-max_len // page_size)) + 1)
        self.prefill_chunk = prefill_chunk
        self.chunk_budget = max(1, chunk_budget)
        self.draft_tier = draft_tier
        self.spec_k = spec_k
        self.prefix_cache = prefix_cache
        if draft_tier is not None and spec_k < 1:
            raise ValueError(f"spec_k must be >= 1 (got {spec_k})")
        self._tier_chunk_fns: dict[str, Any] = {}
        self._tier_verify_fns: dict[str, Any] = {}
        super().__init__(cfg, params, **kw)
        self._alloc = PageAllocator(self.n_pages, page_size)
        self._jobs: list[_ChunkJob] = []
        self._leases: dict[str, PageLease] = {}
        self._paged_stalls = 0
        self._chunks = 0
        self._spec_steps = 0
        self._spec_totals = {"proposed": 0, "accepted": 0, "corrections": 0}
        if draft_tier is not None:
            draft_spec = api.make_spec(cfg, mult=draft_tier)
            self._draft_exec = (
                self._tier_exec[draft_tier]
                if draft_tier in self._tier_exec else api.prepare_params(
                    self.params, cfg, draft_spec, mesh=self.mesh))
            self._draft = self._make_draft(draft_spec)
        else:
            self._draft = None

    # --- device state -----------------------------------------------------

    def _build_state(self) -> None:
        cfg, capacity = self.cfg, self.capacity
        self._arena = PagedArena(cfg, capacity, self.max_len,
                                 self.page_size, self.n_pages)
        self._state = {
            "cache": self._arena.cache,
            "table": jnp.zeros((capacity, self._arena.max_pages),
                               jnp.int32),
            "tok": jnp.zeros((capacity, 1), jnp.int32),
            "temp": jnp.zeros((capacity,), jnp.float32),
            "topk": jnp.zeros((capacity,), jnp.int32),
            "rng": jax.random.split(jax.random.key(self.seed), capacity),
        }
        if cfg.cross_every:
            self._state["img"] = jnp.zeros(
                (capacity, cfg.n_img_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        self._state_sh = self._state_shardings()
        self._state = jax.device_put(self._state, self._state_sh)

    def _state_shardings(self) -> dict:
        from jax.sharding import NamedSharding
        mesh = self.mesh
        sh = {"cache": rules.paged_cache_shardings(
            self._state["cache"], mesh, frozenset(self._arena.paged))}
        sh["table"] = self._replicated()
        for key in ("tok", "temp", "topk"):
            sh[key] = NamedSharding(mesh, rules.batch_pspec(
                key, self._state[key].shape, mesh))
        sh["rng"] = self._replicated()
        if "img" in self._state:
            sh["img"] = NamedSharding(mesh, rules.batch_pspec(
                "img", self._state["img"].shape, mesh))
        return sh

    @property
    def _prefill_shapes(self) -> int:
        """Distinct prefill compile shapes (retrace sanitizer budget):
        one per bucket plus the (1, chunk) first-chunk shape when
        chunking uses a non-bucket length."""
        extra = int(self.prefill_chunk is not None
                    and self.prefill_chunk not in self.buckets)
        return len(self.buckets) + extra

    # --- jitted steps -----------------------------------------------------

    def _slot_leaf_keys(self) -> list[str]:
        return [k for k in sorted(self._arena.cache)
                if k not in self._arena.paged and k != "length"]

    def _make_decode(self, spec):
        """Non-speculative paged decode: gather the dense view, run the
        baseline's exact decode+sample ops, commit each row's one new KV
        row back to its page (inactive lanes write the trash page)."""
        arena = self._arena

        def decode_impl(params, state):
            extras = {"img_embeds": state["img"]} if "img" in state else {}
            cache, table = state["cache"], state["table"]
            old_len = cache["length"]
            view = arena.view(cache, table)
            with ctx.use_rules(self.mesh, rules.logical_rules(self.mesh)):
                logits, new_view = api.decode_step(
                    params, view, state["tok"], self.cfg, spec=spec,
                    extras=extras)
            keys = jax.vmap(lambda k: jax.random.split(k))(state["rng"])
            tok = sampling.sample_tokens(logits[:, -1], state["temp"],
                                         state["topk"], keys[:, 0])
            new_cache = arena.scatter_rows(
                cache, new_view, table, old_len,
                jnp.ones(old_len.shape, bool))
            for key in self._slot_leaf_keys():
                new_cache[key] = new_view[key]
            new_cache["length"] = new_view["length"]
            new = dict(state, cache=new_cache, tok=tok[:, None],
                       rng=keys[:, 1])
            return new, tok

        return jax.jit(decode_impl, donate_argnums=(1,),
                       out_shardings=(self._state_sh, self._replicated()))

    def _extra_tier_fns(self, name: str, spec) -> None:
        self._tier_chunk_fns[name] = self._make_chunk(spec)
        if self.draft_tier is not None:
            self._tier_verify_fns[name] = self._make_verify(spec)

    def _activate(self, name: str) -> None:
        super()._activate(name)
        self._chunk = self._tier_chunk_fns[name]
        self._verify = self._tier_verify_fns.get(name)

    def _make_chunk(self, spec):
        def chunk_impl(params, workspace, tokens, extras, n_valid):
            with ctx.use_rules(self.mesh, rules.logical_rules(self.mesh)):
                return api.chunk_step(params, workspace, tokens, self.cfg,
                                      spec=spec, extras=extras,
                                      n_valid=n_valid)
        return jax.jit(chunk_impl, donate_argnums=(1,))

    def _make_draft(self, spec):
        """Draft `spec_k` greedy tokens per lane on a throwaway gathered
        view — nothing escapes but the proposals, so the draft tier can
        never pollute KV pages."""
        arena, k = self._arena, self.spec_k

        def draft_impl(params, state):
            extras = {"img_embeds": state["img"]} if "img" in state else {}
            view = arena.view(state["cache"], state["table"])

            def draft_body(carry, _):
                v, tok = carry
                logits, v = api.decode_step(params, v, tok, self.cfg,
                                            spec=spec, extras=extras)
                nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
                return (v, nxt[:, None]), nxt

            with ctx.use_rules(self.mesh, rules.logical_rules(self.mesh)):
                _, toks = jax.lax.scan(draft_body, (view, state["tok"]),
                                       None, length=k)
            return jnp.moveaxis(toks, 0, 1)   # (capacity, k)

        return jax.jit(draft_impl, out_shardings=self._replicated())

    def _make_verify(self, spec):
        """Verify `spec_k` drafted tokens in one scan of the serving
        tier's own decode_step.  Per lane: emit the longest agreeing
        prefix + one correction (greedy), or one sampled token (the
        baseline RNG stream); commit only accepted KV rows (rejected
        positions scatter to the trash page); roll non-paged state back
        to the snapshot of the last emitted position."""
        arena, cfg, k = self._arena, self.cfg, self.spec_k
        slot_keys = self._slot_leaf_keys()

        def verify_impl(params, state, draft, k_row):
            extras = {"img_embeds": state["img"]} if "img" in state else {}
            cache, table = state["cache"], state["table"]
            old_len = cache["length"]

            def verify_body(carry, i):
                v, tok = carry
                logits, nv = api.decode_step(params, v, tok, cfg,
                                             spec=spec, extras=extras)
                live = i < k_row                       # (capacity,)
                nv = {key: _sel(live, nv[key], v[key],
                                arena.slot_axes[key])
                      for key in nv}
                nxt = jax.lax.dynamic_index_in_dim(
                    draft, i, axis=1, keepdims=True)   # (capacity, 1)
                snap = {key: nv[key] for key in slot_keys}
                return (nv, jnp.where(live[:, None], nxt, tok)), \
                    (logits[:, -1], snap)

            with ctx.use_rules(self.mesh, rules.logical_rules(self.mesh)):
                (view_k, _), (lgs, snaps) = jax.lax.scan(
                    verify_body, (arena.view(cache, table), state["tok"]),
                    jnp.arange(k))
                # lgs: (k, capacity, vocab) — step i's next-token logits
                e = jnp.argmax(lgs.astype(jnp.float32), axis=-1) \
                    .astype(jnp.int32).T                    # (capacity, k)
                agree = jnp.cumprod((e == draft).astype(jnp.int32), axis=1)
                greedy = state["temp"] <= 0.0
                keys = jax.vmap(lambda r: jax.random.split(r))(state["rng"])
                corr0 = sampling.sample_tokens(
                    lgs[0], state["temp"], state["topk"], keys[:, 0])
                a = jnp.where(greedy, agree.sum(axis=1), 0)
                a = jnp.minimum(a, k_row)
                m = jnp.where(a >= k_row, k_row, a + 1)     # 0 when k_row=0
                e_at_a = jnp.take_along_axis(
                    e, jnp.minimum(a, k - 1)[:, None], axis=1)[:, 0]
                corr = jnp.where(greedy, e_at_a, corr0)
                cols = jnp.arange(k)[None, :]
                emitted = jnp.where(cols < a[:, None], draft, corr[:, None])
                tok_new = jnp.take_along_axis(
                    emitted, jnp.maximum(m - 1, 0)[:, None], axis=1)
                new_cache = dict(cache)
                for i in range(k):
                    new_cache = arena.scatter_rows(
                        new_cache, view_k, table, old_len + i, i < m)
                idx = jnp.maximum(m - 1, 0)
                for key in slot_keys:
                    new_cache[key] = _pick_snap(
                        snaps[key], idx, arena.slot_axes[key])
                new_cache["length"] = old_len + m
                # tok/rng advance unconditionally, exactly like the
                # baseline decode: idle lanes are re-seeded at install,
                # and a sampled lane consumes one split per emitted
                # token in both engines (stream parity)
                new = dict(state, cache=new_cache, tok=tok_new,
                           rng=keys[:, 1])
            return new, (emitted, m, a)

        repl = self._replicated()
        return jax.jit(verify_impl, donate_argnums=(1,),
                       out_shardings=(self._state_sh, (repl, repl, repl)))

    # --- submission / admission -------------------------------------------

    def submit(self, request: Request) -> None:
        sp = request.sampling
        n = len(request.tokens)
        if n >= 1 and sp.max_new_tokens >= 1:
            need = -(-(n + sp.max_new_tokens - 1) // self.page_size)
            if need > self.n_pages - 1:
                raise ValueError(
                    f"{request.request_id}: needs {need} pages, pool has "
                    f"{self.n_pages - 1} usable")
        super().submit(request)

    def _conditioning_digest(self, request: Request) -> str:
        """Prefix-cache key component: extras content (frames / image
        embeddings change KV for identical tokens) + the compute path
        (bucket vs chunk schedule), so only bit-identically produced
        prefixes ever share pages."""
        parts = []
        for key in sorted(request.extras or {}):
            v = np.asarray(request.extras[key])
            parts.append(f"{key}:{v.shape}:"
                         f"{hashlib.sha1(v.tobytes()).hexdigest()[:16]}")
        return "|".join(parts)

    def _flat_idx(self, lease: PageLease, shared_tokens: int, n: int
                  ) -> np.ndarray:
        """Host-built scatter map for the admission insert: position j
        -> pool row.  Prefix-shared positions and everything past the
        prompt go to the trash page (shared pages stay read-only, fresh
        pages stay zero past the prompt — the spec-leak invariant)."""
        ps = self.page_size
        idx = np.full((self.max_len,), TRASH_PAGE, np.int32)
        for j in range(shared_tokens, n):
            idx[j] = lease.pages[j // ps] * ps + j % ps
        return idx

    def _admit_ready(self, now: float) -> None:
        while self._free:
            request = self._sched.peek_ready(now)
            if request is None:
                break
            sp = request.sampling
            n = len(request.tokens)
            rid = request.request_id
            digest = self._conditioning_digest(request)
            chunked = (self.prefill_chunk is not None
                       and n > self.prefill_chunk)
            path = (f"chunk:{self.prefill_chunk}" if chunked
                    else f"bucket:{next(b for b in self.buckets if b >= n)}")
            digest = f"{digest}|{path}"
            lease = self._alloc.alloc(
                rid, n + sp.max_new_tokens - 1,
                prompt=tuple(request.tokens) if self.prefix_cache else None,
                digest=digest)
            if lease is None:
                # FIFO head waits for pages — no overtaking, so arrival
                # order is preserved exactly like the slot engine
                self._paged_stalls += 1
                break
            self._sched.pop_ready(now)
            ready_wall = self._sched.ready_wall(rid)
            slot_id = self._free.pop()
            self._leases[rid] = lease
            try:
                if chunked:
                    self._start_chunked(request, ready_wall, slot_id,
                                        lease, digest)
                else:
                    self._admit(request, ready_wall, slot_id,
                                lease=lease, digest=digest)
            except Exception:
                if self._slots[slot_id] is None:
                    self._free.append(slot_id)
                    self._sched.restore(request, ready_wall)
                    self._alloc.free(rid)
                    self._leases.pop(rid, None)
                raise

    def _admit(self, request: Request, ready_wall: float, slot_id: int,
               lease: PageLease | None = None, digest: str = "") -> None:
        """Whole-prompt admission: the baseline's exact prefill + first-
        token sampling (same bucket, same ops, same RNG), then a paged
        insert instead of a slot insert."""
        sp = request.sampling
        n = len(request.tokens)
        bucket = next(b for b in self.buckets if b >= n)
        with TraceAnnotation(eng.SPAN_ADMIT) as span:
            eng.admission_counters(span, request, n, bucket)
            logits, req_cache, extras = self._prefill_padded(request, bucket)
            key = self._request_key(sp)
            self._admitted += 1
            self._install(request, req_cache, slot_id, lease, n, extras,
                          sp, key, logits, ready_wall, digest)

    def _start_chunked(self, request: Request, ready_wall: float,
                       slot_id: int, lease: PageLease, digest: str) -> None:
        """First chunk of an interleaved prefill: the request takes its
        slot + pages now but joins decode only when the last chunk
        lands; meanwhile every tick decodes the active lanes."""
        sp = request.sampling
        prompt = np.asarray(request.tokens, np.int32)
        c = self.prefill_chunk
        # every chunk runs at length c, the last one padded
        padded_len = -(-len(prompt) // c) * c
        with TraceAnnotation(eng.SPAN_ADMIT) as span:
            eng.admission_counters(span, request, len(prompt), padded_len)
            with TraceAnnotation(eng.SPAN_ADMIT_PREFILL):
                extras = self._prefill_extras(request)
                key = self._request_key(sp)
                self._admitted += 1
                t0 = time.perf_counter()
                logits, workspace = self._prefill(
                    self.exec_params, jnp.asarray(prompt[None, :c]), extras,
                    true_len=jnp.asarray([c], jnp.int32))
                jax.block_until_ready(logits)
                dt = time.perf_counter() - t0
                self._prefill_s += dt
                self._chunks += 1
                if self.meter is not None:
                    self.meter.on_prefill(request.request_id, dt)
            slot = _Slot(request, len(prompt), self._tick, ready_wall,
                         self._admitted)
            slot.prefilling = True
            if self.draft_tier is not None:
                slot.spec_counts = {"proposed": 0, "accepted": 0,
                                    "corrections": 0}
            self._slots[slot_id] = slot
            self._jobs.append(_ChunkJob(request, slot_id, lease, digest,
                                        key, extras, workspace, c))

    def _install(self, request: Request, req_cache: dict, slot_id: int,
                 lease: PageLease, n: int, extras: dict, sp, key,
                 logits: jax.Array, ready_wall: float, digest: str,
                 slot: _Slot | None = None) -> None:
        """Common tail of both admission paths: first-token sample from
        the last prompt position's `logits`, paged insert, device row
        updates, prefix registration, slot record, first emit."""
        rid = request.request_id
        with TraceAnnotation(eng.SPAN_ADMIT_INSTALL):
            first = self._first(logits.astype(jnp.float32),
                                jnp.asarray([sp.temperature], jnp.float32),
                                jnp.asarray([sp.top_k], jnp.int32),
                                key[None])
            flat_idx = self._flat_idx(lease, lease.hit_tokens, n)
            self._arena.cache = self._state["cache"]
            self._arena.insert(req_cache, slot_id, flat_idx)
            self._state["cache"] = self._arena.cache
            row = np.zeros((self._arena.max_pages,), np.int32)
            row[:len(lease.pages)] = lease.pages
            at = jnp.asarray(slot_id)
            self._state = dict(
                self._state,
                table=self._state["table"].at[at].set(jnp.asarray(row)),
                tok=self._state["tok"].at[at].set(first[:, None][0]),
                temp=self._state["temp"].at[at].set(sp.temperature),
                topk=self._state["topk"].at[at].set(sp.top_k),
                rng=self._state["rng"].at[at].set(key))
            if "img" in self._state:
                self._state["img"] = jax.lax.dynamic_update_slice_in_dim(
                    self._state["img"], extras["img_embeds"].astype(
                        self._state["img"].dtype), slot_id, axis=0)
            self._state = jax.device_put(self._state, self._state_sh)
            if self.prefix_cache:
                self._alloc.register_prefix(rid, tuple(request.tokens),
                                            digest)
        with TraceAnnotation(eng.SPAN_ADMIT_FIRST_TOKEN):
            if slot is None:
                slot = _Slot(request, n, self._tick, ready_wall,
                             self._admitted)
                if self.draft_tier is not None:
                    slot.spec_counts = {"proposed": 0, "accepted": 0,
                                        "corrections": 0}
                self._slots[slot_id] = slot
            slot.prefilling = False
            slot.first_wall = time.perf_counter()
            slot.first_tick = self._tick
            if slot.spec_counts is not None:
                slot.spec_counts["corrections"] += 1
                self._spec_totals["corrections"] += 1
            self._emit(slot_id, int(first[0]))

    # --- chunked-prefill advance ------------------------------------------

    def _advance_prefill(self) -> None:
        for _ in range(self.chunk_budget):
            if not self._jobs:
                return
            job = self._jobs[0]
            req = job.request
            over_budget = any(
                b is not None and self._tick - req.arrival + 1 >= b
                for b in (req.deadline_ticks, req.ttft_deadline_ticks))
            if over_budget:
                self._jobs.pop(0)
                self._evict(job.slot_id, "deadline")
                continue
            if self._advance_one(job):
                self._jobs.pop(0)

    def _advance_one(self, job: _ChunkJob) -> bool:
        """Run one chunk; returns True when the prefill finished (first
        token emitted, request joins decode this tick).  Each later chunk
        is an `engine.admit` span of its own, without the counters that
        the first chunk's span carries for the whole admission."""
        with TraceAnnotation(eng.SPAN_ADMIT):
            with TraceAnnotation(eng.SPAN_ADMIT_PREFILL):
                prompt = np.asarray(job.request.tokens, np.int32)
                n = prompt.shape[0]
                c = self.prefill_chunk
                take = min(c, n - job.pos)
                padded = np.zeros((1, c), np.int32)
                padded[0, :take] = prompt[job.pos:job.pos + take]
                t0 = time.perf_counter()
                logits, job.workspace = self._chunk(
                    self.exec_params, job.workspace, jnp.asarray(padded),
                    job.extras, jnp.asarray([take], jnp.int32))
                jax.block_until_ready(logits)
                dt = time.perf_counter() - t0
                self._prefill_s += dt
                self._chunks += 1
                if self.meter is not None:
                    self.meter.on_prefill(job.request.request_id, dt)
            job.pos += take
            if job.pos < n:
                return False
            slot = self._slots[job.slot_id]
            self._install(job.request, job.workspace, job.slot_id,
                          job.lease, n, job.extras, job.request.sampling,
                          job.key, logits[:, take - 1], slot.ready_wall,
                          job.digest, slot=slot)
            return True

    # --- eviction ---------------------------------------------------------

    def _evict(self, slot_id: int, reason: str) -> None:
        slot = self._slots[slot_id]
        rid = slot.request.request_id
        if slot.prefilling:
            # never emitted: TTFT = time waited (the budget it blew)
            slot.first_wall = time.perf_counter()
        super()._evict(slot_id, reason)
        self._alloc.free(rid)
        self._leases.pop(rid, None)
        # neutralize the freed lane on device: with a zero table row and
        # zero length every future write it makes lands in the trash
        # page, so reused pages can never be corrupted by a stale lane
        at = jnp.asarray(slot_id)
        cache = self._state["cache"]
        self._state = dict(
            self._state,
            cache=dict(cache, length=cache["length"].at[at].set(0)),
            table=self._state["table"].at[at].set(
                jnp.zeros((self._arena.max_pages,), jnp.int32)))
        self._state = jax.device_put(self._state, self._state_sh)

    def _slot_of(self, request_id: str) -> int:
        slot_id = next((i for i, s in enumerate(self._slots)
                        if s is not None
                        and s.request.request_id == request_id), None)
        if slot_id is None:
            raise PagingError(
                f"request {request_id!r} is not resident "
                f"(never admitted, finished, or evicted)")
        return slot_id

    # --- copy-on-write ----------------------------------------------------

    def resolve_cow(self, request_id: str, index: int
                    ) -> tuple[int, int] | None:
        """Make block-table entry `index` of `request_id` writable:
        allocator bookkeeping + device page copy + table row update.
        The serving path itself never needs this (decode always writes
        strictly past the last shareable page — see ARCHITECTURE.md);
        it serves fork()-style consumers and the COW tests."""
        op = self._alloc.cow(request_id, index)
        if op is None:
            return None
        src, dst = op
        self._arena.cache = self._state["cache"]
        self._arena.copy_pages([src], [dst])
        self._state["cache"] = self._arena.cache
        slot_id = self._slot_of(request_id)
        self._state = dict(
            self._state,
            table=self._state["table"].at[slot_id, index].set(dst))
        self._state = jax.device_put(self._state, self._state_sh)
        return op

    # --- the serving loop -------------------------------------------------

    def step(self) -> None:
        """One tick: shed expired, advance at most `chunk_budget`
        prefill chunks, admit while slots AND pages allow, then one
        decode (or draft+verify) step over the active lanes."""
        with TraceAnnotation(eng.SPAN_STEP):
            now = self._tick
            with TraceAnnotation(eng.SPAN_SCHEDULE):
                self._sched.note_ready(now, time.perf_counter())
                for request in self._sched.pop_expired(now):
                    self._shed(request)
            self._advance_prefill()
            self._admit_ready(now)
            decoding = [i for i, s in enumerate(self._slots)
                        if s is not None and not s.prefilling]
            if decoding:
                if self._draft is not None:
                    self._spec_step(decoding)
                else:
                    self._decode_step_paged(decoding)
            self._tick += 1

    def _decode_step_paged(self, decoding: list[int]) -> None:
        t0 = time.perf_counter()
        with TraceAnnotation(eng.SPAN_DECODE):
            self._state, tok = self._decode(self.exec_params, self._state)
        self._decode_steps += 1
        with TraceAnnotation(eng.SPAN_READBACK):
            tok_host = np.asarray(tok)
        dt = time.perf_counter() - t0
        self._decode_s += dt
        with TraceAnnotation(eng.SPAN_EMIT):
            if self.meter is not None:
                self.meter.on_decode(
                    dt, [self._slots[i].request.request_id
                         for i in decoding], self.capacity)
            for slot_id in decoding:
                if self._slots[slot_id] is not None:
                    self._emit(slot_id, int(tok_host[slot_id]))

    def _spec_step(self, decoding: list[int]) -> None:
        """Draft + verify one speculative step: greedy lanes emit up to
        `spec_k` accepted drafts + 1 correction, sampled lanes emit one
        baseline-stream token, idle/prefilling lanes are frozen
        (k_row = 0)."""
        with TraceAnnotation(eng.SPAN_DECODE):
            kr = np.zeros((self.capacity,), np.int32)
            for i in decoding:
                slot = self._slots[i]
                sp = slot.request.sampling
                if sp.temperature <= 0.0:
                    kr[i] = min(self.spec_k,
                                sp.max_new_tokens - len(slot.tokens))
                else:
                    kr[i] = 1
            t0 = time.perf_counter()
            draft = self._draft(self._draft_exec, self._state)
            self._state, (emitted, m, a) = self._verify(
                self.exec_params, self._state, draft, jnp.asarray(kr))
        with TraceAnnotation(eng.SPAN_READBACK):
            em = np.asarray(emitted)
            mh = np.asarray(m)
            ah = np.asarray(a)
        self._decode_steps += 1
        self._spec_steps += 1
        dt = time.perf_counter() - t0
        self._decode_s += dt
        with TraceAnnotation(eng.SPAN_EMIT):
            if self.meter is not None:
                self.meter.on_decode(
                    dt, [self._slots[i].request.request_id
                         for i in decoding], self.capacity)
            for i in decoding:
                slot = self._slots[i]
                if slot is None:
                    continue
                if slot.request.sampling.temperature <= 0.0:
                    slot.spec_counts["proposed"] += int(kr[i])
                    self._spec_totals["proposed"] += int(kr[i])
                for j in range(int(mh[i])):
                    field = ("accepted" if j < int(ah[i])
                             else "corrections")
                    # count BEFORE emitting: _emit may evict and freeze
                    # the Completion's SpecStats this very token
                    slot.spec_counts[field] += 1
                    self._spec_totals[field] += 1
                    self._emit(i, int(em[i, j]))
                    if self._slots[i] is None:
                        break

    # --- introspection ----------------------------------------------------

    def debug_kv_rows(self, request_id: str) -> dict:
        """Test/debug surface: the request's dense gathered KV rows per
        paged leaf ((max_len, ...) each), its device length, and how
        many positions its lease actually reserves — everything the
        no-leak invariant check needs."""
        slot_id = self._slot_of(request_id)
        view = self._arena.view(self._state["cache"],
                                self._state["table"])
        out = {}
        for key, axis in self._arena.paged.items():
            rows = jnp.moveaxis(view[key], (axis, axis + 1), (0, 1))
            out[key] = np.asarray(rows[slot_id])
        lease = self._leases[request_id]
        return {"rows": out,
                "length": int(np.asarray(
                    self._state["cache"]["length"])[slot_id]),
                "reserved": len(lease.pages) * self.page_size,
                "shared_tokens": lease.hit_tokens}

    def stats(self) -> dict:
        out = super().stats()
        out["paged"] = {
            **self._alloc.stats(),
            "admission_stalls": self._paged_stalls,
            "max_pages_per_request": self._arena.max_pages,
            "paged_leaves": sorted(self._arena.paged),
            "chunked": {"enabled": self.prefill_chunk is not None,
                        "chunk": self.prefill_chunk,
                        "budget": self.chunk_budget,
                        "chunks": self._chunks,
                        "inflight": len(self._jobs)},
        }
        if self.draft_tier is not None:
            tot = self._spec_totals
            out["spec"] = {
                "draft_tier": self.draft_tier, "k": self.spec_k,
                "steps": self._spec_steps, **tot,
                "acceptance_rate": (tot["accepted"] / tot["proposed"]
                                    if tot["proposed"] else 0.0)}
        extra = [("chunk", self._chunk)]
        if self._draft is not None:
            extra += [("draft", self._draft), ("verify", self._verify)]
        for name, fn in extra:
            if hasattr(fn, "_cache_size"):
                out[f"{name}_compiles"] = fn._cache_size()
        return out


def _sel(live: jax.Array, new, old, batch_axis: int):
    """Per-lane select along `batch_axis` (freeze lanes past k_row)."""
    shape = [1] * new.ndim
    shape[batch_axis] = live.shape[0]
    return jnp.where(live.reshape(shape), new, old)


def _pick_snap(stacked, idx: jax.Array, batch_axis: int):
    """Per-lane snapshot select: `stacked` is (k, *leaf) scan output,
    `idx` (capacity,) picks each lane's last-emitted step."""
    moved = jnp.moveaxis(stacked, batch_axis + 1, 1)   # (k, cap, rest...)
    ix = idx.reshape((1, idx.shape[0]) + (1,) * (moved.ndim - 2))
    picked = jnp.take_along_axis(moved, ix, axis=0)[0]  # (cap, rest...)
    return jnp.moveaxis(picked, 0, batch_axis)
