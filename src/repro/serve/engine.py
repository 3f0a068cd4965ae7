"""Continuous-batching serve engine (DESIGN.md §7, §10).

One fixed-shape slot-batched decode step (`build_slot_decode_step`) serves
every tick: finished requests are evicted and queued ones join by mutating
the donated cache (via the paged pool) and the positions/active vectors —
the compiled computation never changes, so join/evict churn costs zero
recompilation. Prompts run through CHUNKED prefill (fixed chunk shape, one
compile) on pure-attention stacks, whole-prompt prefill otherwise; the
paged pool spills prefilled-but-waiting requests to the host arena and
double-buffers their return (prefetch staged against the decode tick).

Greedy outputs are token-identical to a static whole-batch loop: the slot
decode math is row-independent and chunked prefill is bitwise-equal to
whole-prompt prefill (tests/test_serve_engine.py holds both through churn).

Failure is a handled state, never an exception out of `run()` (DESIGN.md
§10): every request ends in a terminal status (`ok` / `rejected` /
`timeout` / `cancelled` / `failed`). Unservable and load-shed requests are
rejected at submit; per-request deadlines are enforced at every
scheduling boundary; a stall watchdog fails stuck requests instead of
spinning; and a deadline-risk request at the head of the queue may
PREEMPT the youngest active slot — its pages spill back to the host arena
through the pool and it re-queues with tokens intact, resuming
bit-identically when re-admitted. Deadline-aware admission sheds requests
whose latency budget the rolling TTFT/TPOT percentiles say is already
unmeetable. A `FaultInjector` (repro.runtime.inject) can drive tick
faults, forced preemptions, and transient pool exhaustion at
deterministic points.

Token selection is host-side: greedy argmax, or temperature/top-k sampling
with a per-REQUEST deterministic rng (seeded by (engine seed, rid)), so a
request's samples do not depend on which slots it happened to share ticks
with."""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ShapeConfig
from repro.core.lms.planner import MemoryPlan
from repro.models.model import Model
from repro.models.paging import PageArena
from repro.obs import Obs
from repro.runtime.inject import FaultInjector, InjectedFault
from repro.serve.batching import (decode_step_batch, request_prefill_batch,
                                  request_prompt_len)
from repro.serve.kvpool import PagedKVPool
from repro.serve.scheduler import Request, Scheduler
from repro.train.steps import StepSpec, build_slot_decode_step


class ServeEngine:
    def __init__(self, model: Model, mesh, *, slots: int, max_len: int,
                 plan: Optional[MemoryPlan] = None, page_size: int = 16,
                 device_pages: Optional[int] = None,
                 host_pages: Optional[int] = None, prefill_chunk: int = 0,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 eos_id: Optional[int] = None, params=None,
                 kv_dtype: Optional[str] = None, max_queue: int = 0,
                 stall_rounds: int = 64, watchdog_s: Optional[float] = None,
                 preemption: bool = True,
                 injector: Optional[FaultInjector] = None,
                 obs: Optional[Obs] = None):
        cfg = model.cfg
        model._check_servable()
        self.model, self.cfg, self.mesh = model, cfg, mesh
        self.slots, self.max_len = slots, max_len
        self.temperature, self.top_k = temperature, top_k
        self.seed, self.eos_id = seed, eos_id
        # per-engine Obs: a PRIVATE metrics registry (two engines in one
        # process must not cross-contaminate counters) over
        # the process-global span ring (one unified timeline for the trace)
        self.obs = obs if obs is not None else Obs()
        # robustness knobs: stall_rounds bounds consecutive no-progress
        # scheduler rounds before queued work is failed (the watchdog's
        # round-count arm); watchdog_s is its wall-clock arm; preemption
        # enables deadline-risk spill-and-requeue
        self.stall_rounds = stall_rounds
        self.watchdog_s = watchdog_s
        self.preemption = preemption
        self._inj = injector

        paging = plan.kv_paging if plan is not None else None
        # kv_dtype resolution: explicit arg > the planner's priced knob >
        # model width. int8 halves the page budget bytes and the pinned-host
        # arena (pool boundary quantization + per-row scales, DESIGN.md §8).
        # The resolution order and its validation live in ONE place —
        # StepSpec.resolved_kv_dtype() — shared with every step builder, so
        # an unknown priced dtype raises instead of silently degrading.
        spec = StepSpec(plan=plan, donate=True, kv_dtype=kv_dtype)
        kv_dtype = spec.resolved_kv_dtype()
        self.kv_dtype = kv_dtype

        # page-arena geometry must be settled BEFORE the step builds: the
        # decode step's cache signature is the arena layout + page table
        if paging is not None:
            page_size = paging.page_size
            device_pages = (paging.device_pages if device_pages is None
                            else device_pages)
            host_pages = (paging.host_pages if host_pages is None
                          else host_pages)
        # the page grid must tile the cache exactly (see PagedKVPool):
        # snap a non-dividing request down to the largest page size that does
        page_size = math.gcd(max_len, page_size)
        max_pages = max(-(-max_len // page_size), 1)
        full = slots * max_pages
        device_pages = full if device_pages is None else device_pages
        host_pages = 2 * full if host_pages is None else host_pages
        # state-arena depth comes from the plan's priced backlog when there
        # is one (host_pages alone cannot size it for page-free families)
        host_slots = (paging.host_slots if paging is not None
                      and paging.host_slots else 2 * slots)
        arena = PageArena(page_size=page_size, device_pages=device_pages,
                          slots=slots, max_pages=max_pages)

        shape = ShapeConfig("serve_slots", "decode", max_len, slots)
        (self._decode_fn, params_sh, _,
         cache_sh) = build_slot_decode_step(
            model, shape, mesh,
            spec=dataclasses.replace(spec, arena=arena))
        self.pool = PagedKVPool(model, slots=slots, max_len=max_len,
                                page_size=page_size,
                                device_pages=device_pages,
                                host_pages=host_pages,
                                host_slots=host_slots,
                                cache_sharding=cache_sh,
                                kv_dtype=kv_dtype,
                                injector=injector,
                                obs=self.obs)
        self.params = (jax.device_put(model.init(jax.random.key(seed)),
                                      params_sh)
                       if params is None else params)

        # chunked prefill needs absolute-position cache writes — gate to
        # pure-attention stacks; other families prefill the whole prompt.
        # A chunk can never be wider than the cache it writes into.
        self._chunk = (min(prefill_chunk, max_len)
                       if prefill_chunk > 0
                       and all(k == "attn" for k in cfg.layer_kinds())
                       else 0)
        # host-resident params (a plan that streams them) swap in per layer
        # in the prefill scans too
        sched = plan.swap_schedule if plan is not None else None
        if self._chunk:
            self._scratch = model.init_cache(1, max_len)
            self._chunk_fn = jax.jit(
                functools.partial(model.prefill_chunk, stream=sched),
                donate_argnums=(1,))
        self._prefill_fn = jax.jit(
            lambda p, b: model.prefill(p, b, cache_len=max_len,
                                       stream=sched))

        self.scheduler = Scheduler(slots, max_queue=max_queue,
                                   registry=self.obs.registry)
        self._rngs: Dict[int, np.random.Generator] = {}
        self._last_run: List[Request] = []
        # throughput instruments; the legacy `_ticks` / `_decode_tokens` /
        # `_decode_s` / `_wall_s` attributes survive as properties
        reg = self.obs.registry
        self._c_ticks = reg.counter("engine.ticks")
        self._c_decode_tokens = reg.counter("engine.decode_tokens")
        self._c_decode_s = reg.counter("engine.decode_s")
        self._g_wall = reg.gauge("engine.wall_s")

    @property
    def _ticks(self) -> int:
        return int(self._c_ticks.value)

    @property
    def _decode_tokens(self) -> int:
        return int(self._c_decode_tokens.value)

    @property
    def _decode_s(self) -> float:
        return self._c_decode_s.value

    @property
    def _wall_s(self) -> float:
        return self._g_wall.value

    # ---- token selection --------------------------------------------------
    def _select(self, req: Request, row: np.ndarray) -> int:
        t = self.temperature if req.temperature is None else req.temperature
        k = self.top_k if req.top_k is None else req.top_k
        if t <= 0:
            return int(np.argmax(row))
        logp = row.astype(np.float64) / t
        if k and k < logp.size:
            idx = np.argpartition(logp, -k)[-k:]
        else:
            idx = np.arange(logp.size)
        p = np.exp(logp[idx] - logp[idx].max())
        rng = self._rngs.setdefault(
            req.rid, np.random.default_rng((self.seed, req.rid)))
        return int(rng.choice(idx, p=p / p.sum()))

    # ---- prefill ----------------------------------------------------------
    def _prefill(self, req: Request):
        """-> (B=1 cache tree holding the prompt's keys, last-prompt-token
        logits row). Chunked on attention stacks (fixed chunk shape: one
        compile serves every prompt), whole-prompt otherwise."""
        plen = request_prompt_len(self.cfg, req)
        with self.obs.span("engine.prefill", rid=req.rid, tokens=plen,
                           chunked=bool(self._chunk)):
            if self._chunk:
                c = self._chunk
                row = None
                for lo in range(0, plen, c):
                    hi = min(lo + c, plen)
                    batch = request_prefill_batch(self.cfg, req, lo, hi,
                                                  pad_to=c)
                    logits, self._scratch = self._chunk_fn(
                        self.params, self._scratch, batch, jnp.int32(lo),
                        jnp.int32(hi))
                    if hi == plen:
                        row = np.asarray(logits[0, plen - 1 - lo])
                return self._scratch, row
            batch = request_prefill_batch(self.cfg, req)
            logits, cache = self._prefill_fn(self.params, batch)
            return cache, np.asarray(logits[0])

    def _first_token(self, req: Request, row: np.ndarray, t0: float) -> None:
        req.tokens.append(self._select(req, row))
        req.prefilled = True
        now = time.monotonic()
        # TTFT is relative to the request's own arrival when the trace
        # carries one (a streaming workload), else to trace start; a trace
        # timed from zero (arrival == 0.0) is a legitimate arrival, so the
        # unset check is `is None`, never truthiness
        req.ttft_s = now - (t0 if req.arrival is None else req.arrival)
        req.first_tok_mono = now

    def _done(self, req: Request) -> bool:
        return (len(req.tokens) >= req.max_new
                or (self.eos_id is not None and req.tokens
                    and req.tokens[-1] == self.eos_id))

    # ---- lifecycle --------------------------------------------------------
    def _retire(self, req: Request, status: str, error=None) -> None:
        """Terminal transition: free whatever the pool still holds for the
        request (device pages, staged blocks, or host-arena content) and
        record the outcome."""
        self.pool.drop(req.rid)
        if req.done_mono is None:
            req.done_mono = time.monotonic()
        self.scheduler.retire(req, status, error)

    def submit(self, req: Request, t0: Optional[float] = None) -> bool:
        """Admission control. Unservable requests (capacity can never hold
        them) and load-shed submissions (bounded queue full) are REJECTED —
        a terminal status, not an exception — so one bad request cannot
        take down the batch it would have shared ticks with."""
        if req.arrival is None:
            req.arrival = time.monotonic() if t0 is None else t0
        total = request_prompt_len(self.cfg, req) + req.max_new
        if total > self.max_len:
            self._retire(req, "rejected",
                         f"unservable: prompt+max_new={total} exceeds "
                         f"max_len={self.max_len}")
            return False
        need = self.pool.pages_needed(total)
        if need > self.pool.device_pages:
            self._retire(req, "rejected",
                         f"unservable: needs {need} pages, device budget is "
                         f"{self.pool.device_pages}")
            return False
        if not self.scheduler.submit(req):
            self._retire(req, "rejected",
                         f"load shed: queue at max_queue="
                         f"{self.scheduler.max_queue}")
            return False
        return True

    def cancel(self, rid: int) -> bool:
        """Request cancellation of a live request; it retires as
        "cancelled" at the next scheduling boundary."""
        for r in list(self.scheduler.queue) + list(
                self.scheduler.active.values()):
            if r.rid == rid:
                r.cancel()
                return True
        return False

    def _deadline(self, req: Request) -> Optional[float]:
        if req.deadline_s is None or req.arrival is None:
            return None
        return req.arrival + req.deadline_s

    def _est_remaining(self, req: Request) -> Optional[float]:
        """Pessimistic remaining service time from the bounded rolling
        latency windows (p95 TTFT for un-prefilled requests + p95 TPOT per
        remaining token); None until the windows have samples."""
        tpot = self.scheduler.tpot_p95()
        if tpot is None:
            return None
        rem = tpot * max(req.max_new - len(req.tokens), 0)
        if not req.prefilled:
            ttft = self.scheduler.ttft_p95()
            rem += ttft if ttft is not None else 0.0
        return rem

    def _sweep(self, now: float) -> None:
        """Per-round lifecycle sweep: cancellations and blown deadlines, in
        the queue and in the slots."""
        sched = self.scheduler
        for r in list(sched.queue):
            dl = self._deadline(r)
            if r.cancel_requested:
                sched.queue.remove(r)
                self._retire(r, "cancelled", "cancel requested")
            elif dl is not None and now > dl:
                sched.queue.remove(r)
                self._retire(r, "timeout",
                             f"deadline_s={r.deadline_s} blown in queue")
        for slot, r in list(sched.active.items()):
            dl = self._deadline(r)
            if r.cancel_requested:
                sched.evict(slot)
                self._retire(r, "cancelled", "cancel requested")
            elif dl is not None and now > dl:
                sched.evict(slot)
                self._retire(r, "timeout",
                             f"deadline_s={r.deadline_s} blown mid-decode "
                             f"after {len(r.tokens)} tokens")

    def _shed_doomed(self, now: float) -> None:
        """Deadline-aware admission: a queued request whose budget the
        rolling percentiles say cannot be met is shed NOW ("rejected",
        distinguishable from "timeout") instead of burning pages on a
        response that will arrive dead."""
        for r in list(self.scheduler.queue):
            dl = self._deadline(r)
            if dl is None:
                continue
            est = self._est_remaining(r)
            if est is not None and now + est > dl:
                self.scheduler.queue.remove(r)
                self._retire(r, "rejected",
                             f"deadline unmeetable: est {est:.3f}s remaining "
                             f"vs {dl - now:.3f}s budget left")

    # ---- preemption -------------------------------------------------------
    def _pick_victim(self, beneficiary: Optional[Request]) -> Optional[int]:
        """Youngest active slot (latest activation) whose deadline is no
        tighter than the beneficiary's and that has not already been
        preempted (bounds preemption ping-pong)."""
        best_slot, best_seq = None, -1
        bdl = (self._deadline(beneficiary)
               if beneficiary is not None else None)
        for slot, r in self.scheduler.active.items():
            if r.preemptions >= 1:
                continue
            vdl = self._deadline(r)
            if bdl is not None and vdl is not None and vdl < bdl:
                continue
            if r.joined_seq > best_seq:
                best_slot, best_seq = slot, r.joined_seq
        return best_slot

    def _preempt_slot(self, slot: int) -> bool:
        """Spill-and-requeue: the victim's decoded-so-far pages move back
        to the host arena (exact content, via the pool), its reservation
        frees, and it re-queues just behind the queue head with tokens
        intact — resuming later is bit-identical to never having been
        preempted."""
        r = self.scheduler.active[slot]
        cur_len = request_prompt_len(self.cfg, r) + len(r.tokens) - 1
        if not self.pool.preempt(r.rid, cur_len):
            return False               # host arena full: victim decodes on
        self.scheduler.evict(slot)
        self.scheduler.requeue(r, behind=1)
        self.obs.instant("engine.preempt", rid=r.rid, slot=slot,
                         tokens=len(r.tokens))
        return True

    def _maybe_preempt(self, now: float) -> None:
        """A deadline-risk request at the head of the queue may reclaim a
        slot + device pages from the youngest active slot."""
        if not self.preemption or not self.scheduler.queue:
            return
        head = self.scheduler.queue[0]
        dl = self._deadline(head)
        if dl is None:
            return
        need = self.pool.pages_needed(
            request_prompt_len(self.cfg, head) + head.max_new)
        staged = self.pool.status(head.rid) == "staged"
        if (self.scheduler.free_slot() is not None
                and (staged or self.pool._has_dev(need))):
            return                     # admits naturally this round
        est = self._est_remaining(head)
        if est is None or now + est <= dl:
            return                     # no evidence of deadline risk yet
        victim = self._pick_victim(head)
        if victim is not None:
            self._preempt_slot(victim)

    # ---- scheduling -------------------------------------------------------
    def _reserve_need(self, req: Request) -> int:
        total = request_prompt_len(self.cfg, req) + req.max_new
        return self.pool.pages_needed(total)

    def _admit(self, t0: float) -> bool:
        """Two-phase admission (see scheduler.py): FIFO slot joins under the
        device page budget, then prefill-ahead spills into the host arena.
        -> True if anything progressed."""
        pool, sched = self.pool, self.scheduler
        progressed = False
        while sched.queue:
            head = sched.queue[0]
            need = self._reserve_need(head)
            slot = sched.free_slot()
            staged = pool.status(head.rid) in ("staged",)
            if slot is None or not (staged or pool.can_reserve(need)):
                break
            sched.queue.popleft()
            if head.prefilled:
                pool.attach(head.rid, slot)          # return from the spill
            else:
                cache1, row = self._prefill(head)
                self._first_token(head, row, t0)
                if self._done(head):
                    # max_new=1 / eos on the prefill token: finished without
                    # ever needing a slot or pages
                    head.done_mono = time.monotonic()
                    sched.retire(head, "ok")
                    progressed = True
                    continue
                pool.attach_fresh(head.rid, slot, cache1,
                                  request_prompt_len(self.cfg, head), need)
            sched.activate(head, slot)
            progressed = True
        # prefill-ahead: process waiting prompts into the host arena so
        # their pages are ready the moment a slot frees
        for req in list(sched.queue):
            if req.prefilled:
                continue
            plen = request_prompt_len(self.cfg, req)
            if not pool.can_spill(pool.pages_needed(plen)):
                break
            cache1, row = self._prefill(req)
            self._first_token(req, row, t0)
            if self._done(req):
                req.done_mono = time.monotonic()
                sched.queue.remove(req)
                sched.retire(req, "ok")
                progressed = True
                continue
            pool.spill(req.rid, cache1, plen, self._reserve_need(req))
            progressed = True
        return progressed

    def _prefetch_next(self) -> None:
        """Double buffer: stage the first waiting request whose pages are
        spilled back toward the device while the decode tick computes (the
        pool declines when the device budget refuses the claim)."""
        for req in self.scheduler.queue:
            if self.pool.status(req.rid) == "host":
                self.pool.prefetch(req.rid)
                return

    # ---- decode -----------------------------------------------------------
    def _fail_active(self, reason: str) -> None:
        """Batch-level fault: every active request retires as "failed"
        (its pool entry freed) and serving continues with the queue."""
        for slot, r in list(self.scheduler.active.items()):
            self.scheduler.evict(slot)
            self._retire(r, "failed", reason)

    def _tick(self) -> None:
        # injected tick faults fire BEFORE dispatch (a donated cache is
        # never left half-consumed): "raise" fails the active batch in
        # place of crashing run(); "preempt" forces a spill-and-requeue of
        # the youngest slot — the deterministic mid-decode preemption drill
        if self._inj is not None:
            try:
                ev = self._inj.check("engine.tick")
            except InjectedFault as e:
                self._fail_active(str(e))
                return
            if ev is not None and ev.kind == "preempt":
                victim = self._pick_victim(None)
                if victim is not None:
                    self._preempt_slot(victim)
        active = self.scheduler.active
        if not active:
            return
        b = self.slots
        toks = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        act = np.zeros((b,), bool)
        for s, r in active.items():
            toks[s, 0] = r.tokens[-1]
            pos[s] = request_prompt_len(self.cfg, r) + len(r.tokens) - 1
            act[s] = True
        with self.obs.span("engine.tick", batch=len(active)):
            posd = jnp.asarray(pos)
            batch = decode_step_batch(self.cfg, jnp.asarray(toks), posd)
            t0 = time.monotonic()
            logits, self.pool.cache = self._decode_fn(
                self.params, self.pool.cache, batch, posd, jnp.asarray(act))
            # THE tick's one host sync: every slot's next-token row in one
            # pull (all per-request bookkeeping below is host-side numpy)
            rows = np.asarray(logits)  # lint: waive RL004 the single budgeted sync of the tick
            self._c_decode_s.inc(time.monotonic() - t0)
            released = False
            for s, r in active.items():
                tok = self._select(r, rows[s])
                r.tokens.append(tok)
                if self._done(r):
                    r.done_mono = time.monotonic()
                    self.scheduler.finish(s)
                    self.pool.release(r.rid)
                    released = True
            if released:
                # a release is the budget headroom the double buffer needs:
                # stage the next waiting request NOW so its host->device copy
                # runs during token selection / batch build and the coming
                # _admit attaches from the staged block instead of the arena
                self._prefetch_next()
        self._c_ticks.inc()
        self._c_decode_tokens.inc(len(active))

    # ---- driver -----------------------------------------------------------
    def _fail_queued(self, reason: str) -> None:
        sched = self.scheduler
        while sched.queue:
            r = sched.queue.popleft()
            self._retire(r, "failed", reason)

    def run(self, requests: Sequence[Request]) -> Dict[int, np.ndarray]:
        """Serve a request trace to completion; -> {rid: generated token
        ids} for EVERY terminal request (non-ok requests carry whatever
        tokens they produced; check `Request.status`). Never raises for a
        per-request failure. Per-request TTFT and engine throughput land
        in `metrics()`."""
        t0 = time.monotonic()
        for r in requests:
            self.submit(r, t0)
        idle_rounds = 0
        last_progress = time.monotonic()
        while self.scheduler.has_work():
            now = time.monotonic()
            self._sweep(now)
            self._shed_doomed(now)
            self._maybe_preempt(now)
            progressed = self._admit(t0)
            if progressed:
                last_progress = time.monotonic()
            if not self.scheduler.active:
                if progressed:
                    idle_rounds = 0
                    continue
                # stall watchdog: nothing active, nothing admits — give
                # transient conditions (injected exhaustion, arena churn)
                # stall_rounds chances, then fail the stuck work instead of
                # spinning forever or raising out of run()
                idle_rounds += 1
                stalled_wall = (self.watchdog_s is not None
                                and now - last_progress > self.watchdog_s)
                if idle_rounds > self.stall_rounds or stalled_wall:
                    self._fail_queued(
                        "stalled: queue non-empty but nothing admits "
                        "(host arena too small for one request?)")
                continue
            idle_rounds = 0
            self._prefetch_next()
            self._tick()
            last_progress = time.monotonic()
        self._g_wall.set(time.monotonic() - t0)
        done = self.scheduler.drain()
        for r in done:
            self._rngs.pop(r.rid, None)
        self._last_run = done
        return {r.rid: np.asarray(r.tokens, np.int32) for r in done}

    def metrics(self) -> Dict[str, float]:
        """Registry-backed metrics view. The KEY SET is a stable surface
        (regression-tested): re-expressing it over the obs registry must not
        rename or drop anything callers already consume."""
        sched = self.scheduler
        ticks, dtok = self._ticks, self._decode_tokens
        out = {
            # all-time terminal requests; per-status counters alongside.
            # finished Requests themselves are DRAINED each run — only the
            # bounded latency windows and these counters persist, so a
            # long-lived engine's footprint stays flat
            "requests": float(sched.served_total),
            "ticks": float(ticks),
            "decode_tokens": float(dtok),
            "decode_tok_s": (dtok / self._decode_s
                             if self._decode_s else 0.0),
            "mean_concurrency": dtok / ticks if ticks else 0.0,
            "wall_s": self._g_wall.value,
        }
        for k, v in sched.counters.items():
            out[k] = float(v)
        ttft, tpot = sched._ttft, sched._tpot
        if ttft.window:
            out["ttft_mean_s"] = float(ttft.mean())
            out["ttft_p95_s"] = float(ttft.percentile(95))
        if tpot.window:
            out["tpot_p50_s"] = float(tpot.percentile(50))
            out["tpot_p95_s"] = float(tpot.percentile(95))
        out.update({f"pool_{k}": float(v) for k, v in self.pool.stats.items()})
        return out
