"""Continuous-batching inference engine.

The `Engine` turns the model API's (prefill, decode_step) pair into a
request/response service: requests are admitted from a queue into free
slots of a fixed-capacity decode arena (prefill-then-join), every decode
step advances all occupied slots at their own per-slot lengths, and
finished requests (max tokens / EOS) are evicted so their slots can be
reused mid-flight.  All device work happens in three jitted functions —
prefill (one compile per prompt bucket), slot insert, and
decode+sample — whose shapes depend only on (config, capacity, max_len),
never on the traffic, so there are no per-step recompiles.

Approximate-multiplier serving composes transparently: the engine
resolves `cfg.mult` / `cfg.kernel_policy` through `api.make_spec` exactly
like training, so exact and approximate serving share this code path.
Under an approximate spec the engine serves from a persistent weight-plane
cache (`api.prepare_params`): each GEMM weight is quantized — and, for the
XLA path, table-mapped — once at engine construction instead of on every
decode step.

Graceful degradation rides on the same machinery: `tiers=` names an
ordered ladder of multiplier tiers (index 0 = highest accuracy, the
default; later entries trade accuracy for energy/delay, the paper's
knob applied at serve time).  Each tier gets its own resolved spec,
prepared weight planes, and jitted prefill/decode pair at construction;
`set_tier` flips which one serves — an O(1) host-side pointer swap, no
re-quantization, no cache invalidation (the KV/state arena is
tier-independent).  Every emitted token is attributed to the tier that
produced it (`Completion.tier_tokens`), so accuracy exposure under
brownout is auditable.

Request-lifecycle robustness: per-request TTFT/total deadlines (in
ticks) with load-shedding (`finish_reason="shed"`) and mid-decode
deadline eviction (`"deadline"`), and exception-safe admission — a crash
inside prefill re-queues the victim request before propagating, so a
fleet supervisor draining `pending_requests()` off the dead engine never
loses it.

Observability: each phase of `step()` runs inside a profiler span
(`jax.profiler.TraceAnnotation`, named by the `SPAN_*` constants below,
all under the `engine.` prefix), so a trace taken with
`jax.profiler.start_trace` puts the host's phases on the device trace's
clock.  Without a profiler session a span costs well under a
microsecond and records nothing.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import api
from repro.serving import sampling
from repro.serving.arena import SlotArena
from repro.serving.scheduler import Scheduler
from repro.serving.types import (Completion, Request, SamplingParams,
                                 SpecStats)
from repro.sharding import ctx, rules
from repro.train import train_step as ts

#: the whole of one `Engine.step()`
SPAN_STEP = "engine.step"
#: ready-marking, deadline expiry and load shedding at the tick's start
SPAN_SCHEDULE = "engine.schedule"
#: one admission; tagged with `request_id`, `prompt_len` and `bucket`
#: (the prefill's padded length) when a profiler is recording.  On
#: `PagedEngine`'s chunked path each later chunk is one more such span,
#: without counters, around that chunk and, after the last, the install
SPAN_ADMIT = "engine.admit"
#: prompt padding, the prefill dispatch and the wait for it
SPAN_ADMIT_PREFILL = "engine.admit.prefill"
#: the first-token sample dispatch and the request's state install
#: into the decode arena
SPAN_ADMIT_INSTALL = "engine.admit.install"
#: the first token's read-back and emit
SPAN_ADMIT_FIRST_TOKEN = "engine.admit.first_token"
#: the decode step's dispatch
SPAN_DECODE = "engine.decode"
#: the wait for the decode step's tokens and their copy to the host
SPAN_READBACK = "engine.readback"
#: energy metering and the per-slot emit loop, with the streaming
#: callback and evictions
SPAN_EMIT = "engine.emit"


def admission_counters(span: TraceAnnotation, request: Request,
                       prompt_len: int, bucket: int) -> None:
    """Tag an `engine.admit` span with the request and the prompt's
    length before and after padding (only while a profiler records)."""
    if span.is_enabled():
        span.set_metadata(request_id=request.request_id,
                          prompt_len=prompt_len, bucket=bucket)


class _Slot:
    """Host-side record of one occupied arena slot."""

    def __init__(self, request: Request, prompt_len: int, admitted_tick: int,
                 ready_wall: float, admit_seq: int):
        self.request = request
        self.prompt_len = prompt_len
        self.tokens: list[int] = []
        self.admitted_tick = admitted_tick
        self.ready_wall = ready_wall
        self.first_wall = 0.0
        #: engine tick at which the first token was emitted (chunked
        #: prefill emits it later than admitted_tick)
        self.first_tick = admitted_tick
        self.admit_seq = admit_seq            # FIFO drain order
        self.tier_tokens: dict[str, int] = {}
        #: speculative-decode counters ({"proposed", "accepted",
        #: "corrections"}) filled in by the paged engine; None means the
        #: slot was served without speculation (Completion.spec = None)
        self.spec_counts: dict[str, int] | None = None
        #: True while a paged-engine slot is still prefilling in chunks
        #: (occupies a slot + pages, but does not decode or emit yet)
        self.prefilling = False


class Engine:
    """Slot-based continuous-batching engine over `models/api.py`.

    Args:
      cfg: model config (any family: lm / ssm / hybrid / encdec).
      params: model params; initialized from `seed` when None.
      capacity: decode-arena slots (max concurrent requests).
      max_len: arena sequence horizon; prompt_len + max_new_tokens - 1
        must fit.
      prefill_buckets: prompt pad lengths; each bucket compiles prefill
        once.  Default (max_len,) keeps the one-compile-per-phase
        guarantee; pass e.g. (32, 128, 512) to trade a few compiles for
        less padded prefill compute.
      mesh: device mesh (host mesh by default).  Weights, decode caches,
        and sampler state commit onto it under the tensor-parallel rules
        of sharding/rules.py, so a multi-device "model" axis serves
        genuinely tensor-parallel.
      target: `core.target.HardwareTarget` — builds the mesh from the
        target's axes when `mesh` is None (one die == one TP shard).
      seed: engine RNG seed (params init + per-request sampling streams).
      on_token: streaming callback `f(request_id, token_id)`.
      meter: optional `repro.fleet.meter.EnergyMeter` — converts the
        measured prefill/decode step seconds into per-request energy and
        CO2eq (`Completion.carbon`, cumulative counters in `stats()`).
        None (default) serves unmetered at zero added work beyond an
        `is None` check per phase.
      tiers: ordered multiplier-tier ladder for graceful degradation
        (names resolvable by `api.make_spec`, e.g. ("exact", "trunc2x2",
        "trunc4x4")); index 0 serves by default.  None (default) keeps
        the single-tier behavior: one tier named by `cfg.mult`.
    """

    def __init__(self, cfg: ModelConfig, params: Any | None = None, *,
                 capacity: int = 4, max_len: int = 256,
                 prefill_buckets: tuple[int, ...] | None = None,
                 mesh=None, target=None, seed: int = 0,
                 on_token: Callable[[str, int], None] | None = None,
                 meter=None, tiers: tuple[str, ...] | None = None):
        if mesh is None:
            if target is not None:
                mesh = target.make_mesh()
            else:
                from repro.launch.mesh import make_host_mesh
                mesh = make_host_mesh()
        self.cfg, self.mesh, self.seed = cfg, mesh, seed
        self.target = target
        self.capacity, self.max_len = capacity, max_len
        self.buckets = tuple(sorted(prefill_buckets or (max_len,)))
        self.on_token = on_token
        self.meter = meter
        self.tiers = tuple(tiers) if tiers else (cfg.mult or "exact",)
        if len(set(self.tiers)) != len(self.tiers):
            raise ValueError(f"duplicate tier names in {self.tiers}")
        self.params = params if params is not None else api.init_params(
            cfg, jax.random.key(seed))

        self._build_state()

        # Per-tier serving artifacts.  The weight-plane cache is built
        # once per (weight, multiplier) — switching tiers later is a
        # pointer swap, exactly the reuse `api.prepare_params` promises.
        # `self.params` stays raw (bit-identical outputs either way —
        # the cache is a recomputation saving, not an approximation).
        self._tier_specs: dict[str, Any] = {}
        self._tier_exec: dict[str, Any] = {}
        self._tier_prefill_fns: dict[str, Any] = {}
        self._tier_decode_fns: dict[str, Any] = {}
        for name in self.tiers:
            spec = api.make_spec(cfg, mult=name)
            self._tier_specs[name] = spec
            self._tier_exec[name] = api.prepare_params(
                self.params, cfg, spec, mesh=self.mesh)
            self._tier_prefill_fns[name] = ts.make_prefill_step(
                cfg, mesh, max_len=max_len, spec=spec)
            self._tier_decode_fns[name] = self._make_decode(spec)
            self._extra_tier_fns(name, spec)
        self._first = jax.jit(sampling.sample_tokens)

        self._tier = self.tiers[0]
        self._tier_tokens: dict[str, int] = {t: 0 for t in self.tiers}
        self._tier_switches: list[dict] = []
        self._activate(self._tier)

        self._sched = Scheduler()
        self._ids: set[str] = set()
        self._slots: list[_Slot | None] = [None] * capacity
        self._free = list(range(capacity - 1, -1, -1))
        self._tick = 0
        self._decode_steps = 0
        self._admitted = 0
        self._prefill_s = 0.0
        self._decode_s = 0.0
        self._queue_wait_ticks = 0.0
        self._evictions = {"eos": 0, "length": 0}
        self.completions: list[Completion] = []

    def _build_state(self) -> None:
        """Construct the device arena + sampler state and commit it onto
        the mesh.  Overridable: the paged engine replaces the whole-slot
        arena with page pools + block tables while reusing everything
        else (tier artifacts, admission, accounting)."""
        cfg, capacity = self.cfg, self.capacity
        self._arena = SlotArena(cfg, capacity, self.max_len)
        self._state = {
            "cache": self._arena.cache,
            "tok": jnp.zeros((capacity, 1), jnp.int32),
            "temp": jnp.zeros((capacity,), jnp.float32),
            "topk": jnp.zeros((capacity,), jnp.int32),
            "rng": jax.random.split(jax.random.key(self.seed), capacity),
        }
        if cfg.cross_every:
            self._state["img"] = jnp.zeros(
                (capacity, cfg.n_img_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        # commit the state once under the SAME rules the decode step's
        # sharding hints request — caches shard their batch dim on "data"
        # and their kv-head dim on "model" (rules.cache_shardings), the
        # per-slot sampler state shards on "data" where it divides — and
        # pin the decode step's output to that commitment, so every step
        # sees identical shardings (a single compilation, and no
        # replicated-KV fallback on a multi-device mesh).
        self._state_sh = self._state_shardings()
        self._state = jax.device_put(self._state, self._state_sh)

    def _extra_tier_fns(self, name: str, spec) -> None:
        """Hook: build additional per-tier jitted functions (the paged
        engine adds chunked-prefill and speculative-verify steps)."""

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    def _state_shardings(self) -> dict:
        """Rules-driven NamedShardings for the decode-arena state."""
        from jax.sharding import NamedSharding
        mesh = self.mesh
        sh = {"cache": rules.cache_shardings(self._state["cache"], mesh)}
        for key in ("tok", "temp", "topk"):
            sh[key] = NamedSharding(mesh, rules.batch_pspec(
                key, self._state[key].shape, mesh))
        sh["rng"] = self._replicated()   # per-slot PRNG keys: tiny
        if "img" in self._state:
            sh["img"] = NamedSharding(mesh, rules.batch_pspec(
                "img", self._state["img"].shape, mesh))
        return sh

    # --- jitted decode + sample ------------------------------------------

    def _make_decode(self, spec):
        """One jitted decode+sample per tier: the spec is baked into the
        trace (it is a jit-cache-keying pytree), so each tier compiles
        exactly once and tier switches never retrace another tier."""

        def decode_impl(params, state):
            extras = {"img_embeds": state["img"]} if "img" in state else {}
            with ctx.use_rules(self.mesh, rules.logical_rules(self.mesh)):
                logits, cache = api.decode_step(params, state["cache"],
                                                state["tok"], self.cfg,
                                                spec=spec, extras=extras)
            keys = jax.vmap(lambda k: jax.random.split(k))(state["rng"])
            tok = sampling.sample_tokens(logits[:, -1], state["temp"],
                                         state["topk"], keys[:, 0])
            new = dict(state, cache=cache, tok=tok[:, None], rng=keys[:, 1])
            return new, tok

        return jax.jit(decode_impl, donate_argnums=(1,),
                       out_shardings=(self._state_sh, self._replicated()))

    # --- degradation tiers ------------------------------------------------

    @property
    def tier(self) -> str:
        """Name of the multiplier tier currently serving."""
        return self._tier

    @property
    def tier_index(self) -> int:
        return self.tiers.index(self._tier)

    def _activate(self, name: str) -> None:
        """Point the serving hot path at `name`'s artifacts (also used
        by the retrace sanitizer to re-point after wrapping)."""
        self._spec = self._tier_specs[name]
        self.exec_params = self._tier_exec[name]
        self._prefill = self._tier_prefill_fns[name]
        self._decode = self._tier_decode_fns[name]

    def set_tier(self, name: str) -> None:
        """Switch the serving tier (prefill AND decode).  In-flight
        requests keep their KV/state — tokens emitted after the switch
        come from the new tier's multiplier and are attributed to it."""
        if name not in self._tier_specs:
            raise ValueError(
                f"unknown tier {name!r}; engine tiers: {self.tiers}")
        if name == self._tier:
            return
        self._tier_switches.append(
            {"tick": self._tick, "from": self._tier, "to": name})
        self._tier = name
        self._activate(name)

    # --- submission -------------------------------------------------------

    def submit(self, request: Request) -> None:
        """Queue a request for admission at its arrival tick."""
        n = len(request.tokens)
        sp = request.sampling
        if request.request_id in self._ids:
            raise ValueError(
                f"duplicate request_id {request.request_id!r}")
        if n < 1:
            raise ValueError(f"{request.request_id}: empty prompt")
        if n > self.buckets[-1]:
            raise ValueError(
                f"{request.request_id}: prompt len {n} exceeds largest "
                f"prefill bucket {self.buckets[-1]}")
        if sp.max_new_tokens < 1:
            raise ValueError(f"{request.request_id}: max_new_tokens < 1")
        if n + sp.max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"{request.request_id}: prompt {n} + {sp.max_new_tokens} "
                f"new tokens exceeds arena max_len {self.max_len}")
        for field in ("ttft_deadline_ticks", "deadline_ticks"):
            v = getattr(request, field)
            if v is not None and v < 1:
                raise ValueError(f"{request.request_id}: {field} must be "
                                 f">= 1 tick (got {v})")
        self._ids.add(request.request_id)
        self._sched.submit(request)

    # --- admission (prefill-then-join) -----------------------------------

    def _request_key(self, sp: SamplingParams) -> jax.Array:
        if sp.seed is not None:
            return jax.random.key(sp.seed)
        return jax.random.fold_in(jax.random.key(self.seed),
                                  1 + self._admitted)

    def _prefill_extras(self, request: Request) -> dict:
        cfg = self.cfg
        ex = dict(request.extras or {})
        out = {}
        if cfg.family == "encdec":
            frames = ex.get("frames")
            if frames is None:
                frames = jnp.zeros((1, cfg.enc_seq, cfg.d_model),
                                   jnp.dtype(cfg.dtype))
            out["frames"] = jnp.asarray(frames).reshape(
                1, cfg.enc_seq, cfg.d_model)
        if cfg.cross_every:
            img = ex.get("img_embeds")
            if img is None:
                img = jnp.zeros((1, cfg.n_img_tokens, cfg.d_model),
                                jnp.dtype(cfg.dtype))
            out["img_embeds"] = jnp.asarray(img).reshape(
                1, cfg.n_img_tokens, cfg.d_model)
        return out

    def _prefill_padded(self, request: Request, bucket: int
                        ) -> tuple[jax.Array, dict, dict]:
        """Pad the prompt to `bucket`, run the prefill and wait for it,
        inside the `engine.admit.prefill` span; returns (logits, the
        request's cache, the prefill extras)."""
        n = len(request.tokens)
        with TraceAnnotation(SPAN_ADMIT_PREFILL):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = np.asarray(request.tokens, np.int32)
            extras = self._prefill_extras(request)
            t0 = time.perf_counter()
            logits, req_cache = self._prefill(
                self.exec_params, jnp.asarray(padded), extras,
                true_len=jnp.asarray([n], jnp.int32))
            jax.block_until_ready(logits)
            dt = time.perf_counter() - t0
            self._prefill_s += dt
            if self.meter is not None:
                self.meter.on_prefill(request.request_id, dt)
        return logits, req_cache, extras

    def _admit(self, request: Request, ready_wall: float,
               slot_id: int) -> None:
        sp = request.sampling
        n = len(request.tokens)
        bucket = next(b for b in self.buckets if b >= n)
        with TraceAnnotation(SPAN_ADMIT) as span:
            admission_counters(span, request, n, bucket)
            logits, req_cache, extras = self._prefill_padded(request, bucket)
            with TraceAnnotation(SPAN_ADMIT_INSTALL):
                key = self._request_key(sp)
                first = self._first(
                    logits.astype(jnp.float32),
                    jnp.asarray([sp.temperature], jnp.float32),
                    jnp.asarray([sp.top_k], jnp.int32), key[None])
                self._admitted += 1

                self._arena.cache = self._state["cache"]
                self._arena.insert(req_cache, slot_id)
                self._state["cache"] = self._arena.cache
                at = jnp.asarray(slot_id)
                self._state = dict(
                    self._state,
                    tok=self._state["tok"].at[at].set(first[:, None][0]),
                    temp=self._state["temp"].at[at].set(sp.temperature),
                    topk=self._state["topk"].at[at].set(sp.top_k),
                    rng=self._state["rng"].at[at].set(key))
                if "img" in self._state:
                    self._state["img"] = jax.lax.dynamic_update_slice_in_dim(
                        self._state["img"], extras["img_embeds"].astype(
                            self._state["img"].dtype), slot_id, axis=0)
                # re-commit the canonical shardings after the out-of-jit
                # updates (slot insert / .at scatters), so the decode
                # step's jit cache always keys on one sharding layout
                self._state = jax.device_put(self._state, self._state_sh)

            with TraceAnnotation(SPAN_ADMIT_FIRST_TOKEN):
                slot = _Slot(request, n, self._tick, ready_wall,
                             self._admitted)
                slot.first_wall = time.perf_counter()
                self._slots[slot_id] = slot
                self._emit(slot_id, int(first[0]))

    # --- token accounting / eviction -------------------------------------

    def _emit(self, slot_id: int, token: int) -> None:
        slot = self._slots[slot_id]
        slot.tokens.append(token)
        slot.tier_tokens[self._tier] = \
            slot.tier_tokens.get(self._tier, 0) + 1
        self._tier_tokens[self._tier] += 1
        if self.on_token is not None:
            self.on_token(slot.request.request_id, token)
        sp = slot.request.sampling
        req = slot.request
        if sp.eos_id >= 0 and token == sp.eos_id:
            self._evict(slot_id, "eos")
        elif len(slot.tokens) >= sp.max_new_tokens:
            self._evict(slot_id, "length")
        elif req.deadline_ticks is not None and \
                self._tick - req.arrival + 1 >= req.deadline_ticks:
            # total budget exhausted: keep the partial generation, free
            # the slot for work that can still finish in time
            self._evict(slot_id, "deadline")

    def _evict(self, slot_id: int, reason: str) -> None:
        slot = self._slots[slot_id]
        now = time.perf_counter()
        self._evictions[reason] = self._evictions.get(reason, 0) + 1
        self._queue_wait_ticks += max(
            0.0, slot.admitted_tick - slot.request.arrival)
        self.completions.append(Completion(
            request_id=slot.request.request_id,
            prompt_len=slot.prompt_len,
            tokens=slot.tokens,
            finish_reason=reason,
            arrival=slot.request.arrival,
            admitted_tick=slot.admitted_tick,
            finished_tick=self._tick,
            ttft_s=slot.first_wall - slot.ready_wall,
            ttft_ticks=slot.first_tick - slot.request.arrival + 1.0,
            latency_s=now - slot.ready_wall,
            carbon=(self.meter.finalize(slot.request.request_id,
                                        len(slot.tokens))
                    if self.meter is not None else None),
            attempt=slot.request.attempt,
            tier_tokens=dict(slot.tier_tokens),
            spec=(SpecStats(**slot.spec_counts)
                  if slot.spec_counts is not None else None)))
        self._slots[slot_id] = None
        self._free.append(slot_id)

    def _shed(self, request: Request) -> None:
        """Complete a never-admitted request whose deadline is already
        unmeetable (load shedding at admission)."""
        self._evictions["shed"] = self._evictions.get("shed", 0) + 1
        self._sched._ready_wall.pop(request.request_id, None)
        self.completions.append(Completion(
            request_id=request.request_id,
            prompt_len=len(request.tokens),
            tokens=[],
            finish_reason="shed",
            arrival=request.arrival,
            admitted_tick=-1,
            finished_tick=self._tick,
            ttft_s=0.0,
            latency_s=0.0,
            carbon=(self.meter.finalize(request.request_id, 0)
                    if self.meter is not None else None),
            attempt=request.attempt,
            tier_tokens={}))

    # --- the serving loop -------------------------------------------------

    @property
    def tick(self) -> int:
        """Current virtual-clock tick (one decode step per tick)."""
        return self._tick

    @property
    def n_active(self) -> int:
        return self.capacity - len(self._free)

    @property
    def n_queued(self) -> int:
        return len(self._sched)

    def pending_requests(self) -> list[Request]:
        """Every submitted-but-unfinished request in FIFO order:
        in-flight slot occupants by admission order first, then the
        waiting queue by (arrival, submission) order.  This is the drain
        surface a fleet supervisor uses to re-queue work off a dead
        replica — requests, not partial generations, so a re-served
        request regenerates from scratch; the ordering guarantees a
        failover preserves arrival FIFO on the surviving replicas."""
        active = sorted((s for s in self._slots if s is not None),
                        key=lambda s: s.admit_seq)
        out = [s.request for s in active]
        out.extend(self._sched.pending())
        return out

    def active_request_ids(self) -> set[str]:
        """Ids currently holding arena slots (admitted, unfinished) —
        the diff surface a supervisor uses to wall-clock-stamp
        admissions without reaching into slot internals."""
        return {s.request.request_id for s in self._slots if s is not None}

    def step(self) -> None:
        """One engine tick: shed dead-on-arrival requests, admit due
        requests into free slots, then run one decode step across the
        whole arena."""
        with TraceAnnotation(SPAN_STEP):
            now = self._tick
            with TraceAnnotation(SPAN_SCHEDULE):
                self._sched.note_ready(now, time.perf_counter())
                for request in self._sched.pop_expired(now):
                    self._shed(request)
            while self._free:
                request = self._sched.pop_ready(now)
                if request is None:
                    break
                ready_wall = self._sched.ready_wall(request.request_id)
                slot_id = self._free.pop()
                try:
                    self._admit(request, ready_wall, slot_id)
                except Exception:
                    # crash mid-prefill/insert: restore the host-side
                    # queue state so pending_requests() still drains the
                    # victim — the supervisor re-queues it elsewhere
                    # (device state dies with the engine)
                    if self._slots[slot_id] is None:
                        self._free.append(slot_id)
                        self._sched.restore(request, ready_wall)
                    raise
            if self.n_active:
                t0 = time.perf_counter()
                with TraceAnnotation(SPAN_DECODE):
                    self._state, tok = self._decode(self.exec_params,
                                                    self._state)
                self._decode_steps += 1
                with TraceAnnotation(SPAN_READBACK):
                    tok_host = np.asarray(tok)          # syncs the step
                dt = time.perf_counter() - t0
                self._decode_s += dt
                with TraceAnnotation(SPAN_EMIT):
                    if self.meter is not None:
                        # charge BEFORE emitting: a request evicted this
                        # step must carry this step's share of the energy
                        self.meter.on_decode(
                            dt, [s.request.request_id for s in self._slots
                                 if s is not None], self.capacity)
                    for slot_id in range(self.capacity):
                        if self._slots[slot_id] is not None:
                            self._emit(slot_id, int(tok_host[slot_id]))
            self._tick += 1

    def run_until_complete(self) -> list[Completion]:
        """Drive step() until the queue and the arena are both empty;
        idle ticks fast-forward to the next arrival."""
        while self.n_queued or self.n_active:
            if not self.n_active:
                nxt = self._sched.next_arrival()
                if nxt is not None and nxt > self._tick:
                    self._tick = int(math.ceil(nxt))
            self.step()
        return self.completions

    def stats(self) -> dict:
        done = len(self.completions)
        out = {"ticks": self._tick, "decode_steps": self._decode_steps,
               "admitted": self._admitted,
               "completed": done,
               "prefill_s": self._prefill_s, "decode_s": self._decode_s,
               # admission-queue wait (arrival -> admitted, in ticks) and
               # why slots were reclaimed — the signals a capacity planner
               # needs (a rising queue wait means the arena is the
               # bottleneck, not the model)
               "queue_wait_ticks_total": self._queue_wait_ticks,
               "queue_wait_ticks_mean":
                   self._queue_wait_ticks / done if done else 0.0,
               "evictions": dict(self._evictions),
               "mesh": {ax: int(sz) for ax, sz in self.mesh.shape.items()},
               # accuracy-exposure audit: tokens served per multiplier
               # tier plus the switch log (empty while single-tier)
               "tiers": {"active": self._tier,
                         "ladder": list(self.tiers),
                         "tokens": dict(self._tier_tokens),
                         "switches": list(self._tier_switches)}}
        if self.meter is not None:
            out["carbon"] = self.meter.summary()
        for name, fn in (("prefill", self._prefill),
                         ("decode", self._decode)):
            if hasattr(fn, "_cache_size"):
                out[f"{name}_compiles"] = fn._cache_size()
        return out
