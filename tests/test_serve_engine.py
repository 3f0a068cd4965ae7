"""Serving engine (DESIGN.md §7): greedy token parity between the
continuous-batching engine and the static whole-batch loop through
join/evict churn with real page spill/return, paged-pool round trips,
chunked-prefill exactness, sampling determinism, and the serve-plan
schedule invariant."""
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from repro import compat
from repro import hw as hwlib
from repro.config.base import LMSConfig, MeshSpec, ShapeConfig
from repro.configs import get_config, get_smoke_config
from repro.core.lms.planner import (check_schedule_invariant,
                                    plan_serve_memory, price_kv_paging)
from repro.launch.mesh import make_mesh
from repro.launch.serve import run_static
from repro.models.model import Model
from repro.serve import PagedKVPool, ServeEngine, synth_requests
from repro.train.steps import build_prefill_step, build_slot_decode_step

N_REQ, PROMPT, GEN = 5, 8, 8
TOTAL = PROMPT + GEN          # page grid must tile the cache: PAGE | TOTAL
SLOTS, PAGE, CHUNK = 2, 4, 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("olmo-1b")
    mesh = make_mesh(MeshSpec((1, 1), ("data", "model")))
    model = Model(cfg, attn_impl="naive")
    rng = np.random.default_rng(7)
    reqs = synth_requests(cfg, N_REQ, PROMPT, GEN, rng)
    params, static_toks, _ = run_static(model, mesh, reqs, PROMPT, GEN)
    return cfg, mesh, model, reqs, params, static_toks


def _fresh_requests(reqs):
    """Requests carry engine-mutated state (generated tokens); each engine
    run gets a pristine copy of the same trace."""
    import copy
    out = copy.deepcopy(reqs)
    for r in out:
        r.tokens, r.prefilled, r.ttft_s = [], False, None
        r.arrival, r.first_tok_mono, r.done_mono = None, None, None
        r.status, r.error, r.joined_seq = "queued", None, -1
        r.preemptions, r.cancel_requested = 0, False
    return out


# ---------------------------------------------------------------------------
# The acceptance gate: engine == static loop, token-identical, while the
# trace's aggregate KV footprint exceeds the device page budget
# ---------------------------------------------------------------------------

def test_engine_matches_static_through_churn(setup):
    cfg, mesh, model, reqs, params, static_toks = setup
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                      page_size=PAGE, prefill_chunk=CHUNK, params=params)
    demand = sum(eng.pool.pages_needed(PROMPT + GEN) for _ in reqs)
    assert demand > eng.pool.device_pages, \
        "trace must overflow the device page budget for this test to bite"
    results = eng.run(_fresh_requests(reqs))
    assert set(results) == {r.rid for r in reqs}
    for i, r in enumerate(reqs):
        assert np.array_equal(results[r.rid], static_toks[i]), \
            f"request {r.rid}: engine tokens diverged from static loop"
    # pages genuinely spilled to host and returned — every spilled page
    # comes back, some via the double-buffered staging path
    st = eng.pool.stats
    assert st["spilled_pages"] > 0
    assert st["fetched_pages"] + st["prefetched_pages"] == st["spilled_pages"]
    assert st["prefetched_pages"] > 0, \
        "releases must trigger staged (double-buffered) returns"
    assert st["peak_resident_pages"] <= eng.pool.device_pages


def test_slot_decode_step_matches_whole_batch(setup):
    """One slot-batched step at a uniform position == the whole-batch
    decode step, bit for bit (the row-independence the engine builds on)."""
    from repro.train.steps import build_decode_step
    cfg, mesh, model, reqs, params, _ = setup
    shape = ShapeConfig("d", "decode", TOTAL, 3)
    pshape = ShapeConfig("p", "prefill", PROMPT, 3)
    pfn, _, _, _ = build_prefill_step(model, pshape, mesh, cache_len=TOTAL)
    toks3 = jnp.asarray(np.stack([r.prompt for r in reqs[:3]]))
    logits, cache = pfn(params, {"tokens": toks3})
    dfn, _, _, _ = build_decode_step(model, shape, mesh, donate=False)
    sfn, _, _, _ = build_slot_decode_step(model, shape, mesh, donate=False)
    t = jnp.argmax(logits, -1)[:, None]
    l1, c1 = dfn(params, cache, {"tokens": t}, jnp.int32(PROMPT))
    l2, c2 = sfn(params, cache, {"tokens": t},
                 jnp.full((3,), PROMPT, jnp.int32), jnp.ones((3,), bool))
    assert jnp.array_equal(l1, l2)
    for a, b in zip(jtu.tree_leaves(c1), jtu.tree_leaves(c2)):
        assert jnp.array_equal(a, b)


def test_chunked_prefill_bitwise_equals_full(setup):
    cfg, mesh, model, reqs, params, _ = setup
    toks = jnp.asarray(np.stack([r.prompt for r in reqs[:2]]))
    full_logits, full_cache = jax.jit(
        lambda p, b: model.prefill(p, b, cache_len=TOTAL))(
            params, {"tokens": toks})
    cache = model.init_cache(2, TOTAL)
    for lo in range(0, PROMPT, CHUNK):
        hi = min(lo + CHUNK, PROMPT)
        lg, cache = jax.jit(model.prefill_chunk)(
            params, cache, {"tokens": toks[:, lo:hi]}, jnp.int32(lo),
            jnp.int32(hi))
    assert jnp.array_equal(lg[:, PROMPT - 1 - lo], full_logits)
    for a, b in zip(jtu.tree_leaves(cache), jtu.tree_leaves(full_cache)):
        assert jnp.array_equal(a, b)


def test_sampling_deterministic_and_bounded(setup):
    cfg, mesh, model, reqs, params, _ = setup
    outs = []
    for _ in range(2):
        eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                          page_size=PAGE, prefill_chunk=CHUNK,
                          temperature=0.9, top_k=5, seed=3, params=params)
        outs.append(eng.run(_fresh_requests(reqs)))
    for rid in outs[0]:
        assert np.array_equal(outs[0][rid], outs[1][rid]), \
            "per-request sampling rng must be deterministic"
        assert outs[0][rid].shape == (GEN,)
        assert (outs[0][rid] >= 0).all() and (outs[0][rid] < cfg.vocab_size).all()


def test_engine_max_new_one_matches_static(setup):
    """A request satisfied by its prefill token must finish without a slot
    or a decode tick — and a page size that does not divide max_len snaps
    down to one that does instead of crashing spill's page reshape."""
    cfg, mesh, model, reqs, params, _ = setup
    one = _fresh_requests(reqs)
    for r in one:
        r.max_new = 1
    _, static1, _ = run_static(model, mesh, reqs, PROMPT, 1, params=params)
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=PROMPT + 1,
                      page_size=4, prefill_chunk=CHUNK, params=params)
    assert eng.pool.page_size == 1          # gcd(9, 4) snap
    results = eng.run(one)
    assert eng._ticks == 0
    for i, r in enumerate(reqs):
        assert np.array_equal(results[r.rid], static1[i])


# ---------------------------------------------------------------------------
# Paged pool
# ---------------------------------------------------------------------------

def test_pool_rejects_ragged_page_grid(setup):
    cfg, mesh, model, _, _, _ = setup
    with pytest.raises(ValueError, match="divide"):
        PagedKVPool(model, slots=1, max_len=14, page_size=4,
                    device_pages=4, host_pages=4)

def _gather_slot(pool, leaf, info, slot, n_pages):
    """Assemble slot `slot`'s first n_pages of content from the arena
    through the pool's page table (the tests' view of the paged layout)."""
    ids = np.asarray(pool.cache["page_table"])[slot, :n_pages]
    assert np.all(ids != pool.null_page), "content pages must be mapped"
    if info.stacked:
        g = np.asarray(leaf)[:, ids]            # [L, n, ps, ...]
        return g.reshape((g.shape[0], n_pages * pool.page_size)
                         + g.shape[3:])
    g = np.asarray(leaf)[ids]                   # [n, ps, ...]
    return g.reshape((n_pages * pool.page_size,) + g.shape[2:])


def test_pool_spill_attach_roundtrip(setup):
    cfg, mesh, model, _, _, _ = setup
    pool = PagedKVPool(model, slots=SLOTS, max_len=TOTAL, page_size=PAGE,
                       device_pages=2 * pool_pages(TOTAL, PAGE),
                       host_pages=8)
    rng = np.random.default_rng(0)
    req_cache = compat.tree.map(
        lambda z: jnp.asarray(rng.standard_normal(z.shape), z.dtype),
        model.init_cache(1, TOTAL))
    n = pool.pages_needed(PROMPT)
    reserve = pool.pages_needed(TOTAL)
    pool.spill(7, req_cache, PROMPT, reserve)
    assert pool.stats["spilled_pages"] == n
    assert not pool.can_spill(pool._host[next(iter(pool._host))].shape[0])
    pool.attach(7, slot=1)
    assert pool.status(7) == "dev"
    # the slot's table row maps its FULL reservation (decode grows into it)
    row = np.asarray(pool.cache["page_table"])[1]
    assert np.all(row[:reserve] != pool.null_page)
    assert np.all(row[reserve:] == pool.null_page)
    # gathering slot 1 through the table recovers the content region exactly
    flat_req = dict(_flat(req_cache))
    for keys, leaf in _flat(pool.cache):
        if keys == ("page_table",):
            continue
        info = pool._info[keys]
        src = flat_req[keys]
        if info.paged:
            w = n * PAGE
            got = _gather_slot(pool, leaf, info, 1, n)
            want = src[:, 0, :w] if info.stacked else src[0, :w]
        else:
            got = leaf[:, 1] if info.stacked else leaf[1]
            want = src[:, 0] if info.stacked else src[0]
        assert np.array_equal(np.asarray(got), np.asarray(want)), keys
    # attach was addressing only: no paged-leaf slot repack ever happens
    assert pool.stats["repack_pages"] == 0
    pool.release(7)
    assert pool.resident_pages == 0
    assert np.all(np.asarray(pool.cache["page_table"])[1] == pool.null_page)


def test_pool_prefetch_stages_against_budget(setup):
    cfg, mesh, model, _, _, _ = setup
    per = pool_pages(TOTAL, PAGE)
    pool = PagedKVPool(model, slots=SLOTS, max_len=TOTAL, page_size=PAGE,
                       device_pages=per, host_pages=8)
    req_cache = model.init_cache(1, TOTAL)
    pool.spill(1, req_cache, PROMPT, per)
    pool.spill(2, req_cache, PROMPT, per)
    assert pool.prefetch(1)                       # fits: budget is free
    assert pool.status(1) == "staged"
    assert not pool.prefetch(2), "second reservation must exceed the budget"
    pool.attach(1, slot=0)
    assert pool.stats["prefetched_pages"] > 0
    assert pool.resident_pages == per


# ---------------------------------------------------------------------------
# Fragmentation: pages are the unit of ADDRESSING — interleaved churn
# scatters a request's pages non-contiguously and nothing may care
# ---------------------------------------------------------------------------

def test_pool_staged_attach_is_pure_table_edit(setup):
    """After prefetch, attach must not touch the paged arenas at all: the
    SAME device buffers (object identity) before and after, zero repack
    copies — the pointer-write contract of the page-table layout. And the
    LIFO free list hands a churned request genuinely scattered arena rows
    whose gathered content still round-trips exactly."""
    cfg, mesh, model, _, _, _ = setup
    rng = np.random.default_rng(3)

    def rand_cache():
        return compat.tree.map(
            lambda z: jnp.asarray(rng.standard_normal(z.shape), z.dtype),
            model.init_cache(1, TOTAL))

    half = pool_pages(PROMPT, PAGE)              # 2 pages of content
    full = pool_pages(TOTAL, PAGE)               # 4-page reservation
    pool = PagedKVPool(model, slots=3, max_len=TOTAL, page_size=PAGE,
                       device_pages=4 * half, host_pages=16)
    # three half reservations carve up the arena...
    for rid, slot in ((1, 0), (2, 1), (3, 2)):
        pool.attach_fresh(rid, slot, rand_cache(), PROMPT, half)
    # ...then releasing the 1st and 3rd leaves non-adjacent free pairs
    pool.release(1)
    pool.release(3)
    spilled = rand_cache()
    pool.spill(9, spilled, PROMPT, full)
    assert pool.prefetch(9)
    paged_before = {keys: leaf for keys, leaf in _flat(pool.cache)
                    if keys != ("page_table",) and pool._info[keys].paged}
    pool.attach(9, slot=0)
    for keys, leaf in _flat(pool.cache):
        if keys in paged_before:
            assert leaf is paged_before[keys], \
                f"staged attach copied paged leaf {keys}"
    assert pool.stats["repack_pages"] == 0
    # the reservation spans both free fragments: a non-contiguous row
    row = np.asarray(pool.cache["page_table"])[0, :full]
    assert np.all(row != pool.null_page)
    assert np.any(np.diff(row) != 1), f"pages unexpectedly contiguous: {row}"
    # and the scattered pages still gather back to the exact content
    flat_req = dict(_flat(spilled))
    for keys, leaf in paged_before.items():
        got = _gather_slot(pool, leaf, pool._info[keys], 0, half)
        src = flat_req[keys]
        w = half * PAGE
        want = src[:, 0, :w] if pool._info[keys].stacked else src[0, :w]
        assert np.array_equal(got, np.asarray(want)), keys


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_engine_parity_under_fragmentation(setup, kv_dtype):
    """Staggered max_new forces interleaved finish/join order, so the LIFO
    free list scatters later requests' pages across the arena. Greedy
    tokens must be identical to an unfragmented serve of the same trace
    (and, at model width, to the static whole-batch loop), with attach
    performing zero paged-leaf copies throughout."""
    cfg, mesh, model, reqs, params, static_toks = setup
    lens = [3 + (2 * i) % 6 for i in range(len(reqs))]   # 3,5,7,3,5 <= GEN

    def varied():
        out = _fresh_requests(reqs)
        for r, n in zip(out, lens):
            r.max_new = n
        return out

    churn = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                        page_size=PAGE, prefill_chunk=CHUNK, params=params,
                        kv_dtype=kv_dtype)
    rows = []
    orig_attach = churn.pool.attach
    def spy(rid, slot):
        orig_attach(rid, slot)
        rows.append(np.asarray(churn.pool.cache["page_table"])[slot].copy())
    churn.pool.attach = spy
    out_churn = churn.run(varied())
    st = churn.pool.stats
    assert st["spilled_requests"] > 0, "trace must churn through the spill"
    assert st["repack_pages"] == 0, "attach repacked paged leaves"
    mapped = [r[r != churn.pool.null_page] for r in rows]
    assert any(len(m) > 1 and np.any(np.diff(m) != 1) for m in mapped), \
        f"churn never scattered a table row: {mapped}"
    # oracle: the same trace with every request resident from the start
    # (enough slots + pages -> no spill, no fragmentation)
    calm = ServeEngine(model, mesh, slots=len(reqs), max_len=TOTAL,
                       page_size=PAGE, prefill_chunk=CHUNK, params=params,
                       kv_dtype=kv_dtype)
    out_calm = calm.run(varied())
    assert calm.pool.stats["spilled_requests"] == 0
    for i, r in enumerate(reqs):
        assert np.array_equal(out_churn[r.rid], out_calm[r.rid]), \
            f"request {r.rid}: fragmentation changed greedy tokens"
        if kv_dtype == "model":
            # greedy decode is prefix-stable: the static loop's first
            # max_new tokens are the oracle at model width
            assert np.array_equal(out_churn[r.rid], static_toks[i][:lens[i]])


def test_engine_tpot_metrics(setup):
    """TPOT percentiles: present, sane, and consistent with the stamps."""
    cfg, mesh, model, reqs, params, _ = setup
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                      page_size=PAGE, prefill_chunk=CHUNK, params=params)
    eng.run(_fresh_requests(reqs))
    m = eng.metrics()
    assert m["requests"] == len(reqs)
    assert 0.0 < m["tpot_p50_s"] <= m["tpot_p95_s"]
    assert m["ttft_p95_s"] > 0.0
    assert m["ok"] == len(reqs)
    # terminal requests are DRAINED from the scheduler (bounded memory);
    # the engine keeps the most recent run's batch for inspection
    assert eng.scheduler.finished == []
    assert len(eng._last_run) == len(reqs)
    for r in eng._last_run:
        assert r.status == "ok"
        assert r.first_tok_mono is not None and r.done_mono is not None
        assert r.done_mono >= r.first_tok_mono


def test_engine_metrics_keys_stable_over_registry(setup):
    """metrics() is a stable surface: re-expressing it over the obs
    registry (DESIGN.md §12) must keep the exact key set callers consume
    (launch/serve.py, downstream dashboards)."""
    cfg, mesh, model, reqs, params, _ = setup
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                      page_size=PAGE, prefill_chunk=CHUNK, params=params)
    eng.run(_fresh_requests(reqs))
    m = eng.metrics()
    expected = {"requests", "ticks", "decode_tokens", "decode_tok_s",
                "mean_concurrency", "wall_s",
                "ok", "rejected", "timeout", "cancelled", "failed",
                "preempted", "ttft_mean_s", "ttft_p95_s",
                "tpot_p50_s", "tpot_p95_s"}
    pool_keys = {f"pool_{k}" for k in eng.pool.stats}
    assert expected | pool_keys <= set(m.keys())
    assert all(isinstance(v, float) for v in m.values())
    # the registry holds the same values under its own (dotted) names
    snap = eng.obs.registry.snapshot()
    assert snap["counters"]["engine.ticks"] == m["ticks"]
    assert snap["counters"]["engine.req.ok"] == m["ok"]
    assert snap["counters"]["engine.requests"] == m["requests"]
    assert snap["gauges"]["engine.wall_s"] == m["wall_s"]
    assert snap["histograms"]["engine.ttft_s"]["p95"] == \
        pytest.approx(m["ttft_p95_s"])
    # and the run left real swap spans on the shared timeline
    from repro.obs import get_obs
    sites = [e.site for e in get_obs().ring.events()]
    assert any(s.startswith(("pool.", "lms.swap")) for s in sites)
    assert any(s == "engine.tick" for s in sites)


def test_engine_arrival_zero_is_preserved(setup):
    """arrival == 0.0 is a legitimate trace-relative timestamp: the engine
    must not overwrite it with trace start (the old `or t0` bug), which
    inflated TTFT to absolute-clock scale."""
    cfg, mesh, model, reqs, params, _ = setup
    trace = _fresh_requests(reqs)
    for r in trace:
        r.arrival = 0.0
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                      page_size=PAGE, prefill_chunk=CHUNK, params=params)
    eng.run(trace)
    for r in eng._last_run:
        assert r.arrival == 0.0, "engine clobbered an explicit arrival"
        # monotonic 'now' minus 0.0 -> absolute clock scale, far above any
        # real TTFT this smoke trace could produce
        assert r.ttft_s > 1.0


def pool_pages(total, page):
    return -(-total // page)


def _flat(tree):
    flat, _ = jtu.tree_flatten_with_path(tree)
    return [(tuple(getattr(e, "key", str(e)) for e in p), v)
            for p, v in flat]


# ---------------------------------------------------------------------------
# Planner: serve plans require the paging executor
# ---------------------------------------------------------------------------

def test_serve_plan_requires_paging_executor():
    cfg = get_config("olmo-1b")
    shape = ShapeConfig("serve", "decode", 4096, 16)
    mesh = MeshSpec((1, 1), ("data", "model"))
    plan = plan_serve_memory(cfg, shape, mesh,
                             LMSConfig(hbm_budget=4 * 1024 ** 3),
                             hwlib.TPU_V5E, slots=16, backlog_slots=32)
    assert plan.residency["kvcache"] == "host"
    assert plan.kv_paging is not None
    assert plan.swap_schedule is not None
    assert plan.swap_schedule.streams_kvcache
    assert plan.swap_schedule.bytes_for("kvcache") > 0
    assert plan.kv_paging.device_pages > 0
    # the invariant: same residency WITHOUT the declared pool must refuse
    with pytest.raises(AssertionError, match="paged-pool executor"):
        check_schedule_invariant(plan.residency, plan.swap_schedule,
                                 serve=True, kv_paging=None)
    # declared pool passes; non-serve (static decode) plans keep the old
    # contract where the per-layer decode stream is the executor
    check_schedule_invariant(plan.residency, plan.swap_schedule,
                             serve=True, kv_paging=plan.kv_paging)
    check_schedule_invariant(plan.residency, plan.swap_schedule)


def test_engine_sized_from_serve_plan(setup):
    """plan_serve_memory -> kv_paging -> pool: the engine takes its page
    budget from the plan and still serves the trace correctly."""
    cfg, mesh, model, reqs, params, static_toks = setup
    mspec = MeshSpec((1, 1), ("data", "model"))
    shape = ShapeConfig("serve", "decode", TOTAL, SLOTS)
    plan = plan_serve_memory(cfg, shape, mspec,
                             LMSConfig(hbm_budget=250 * 1024), slots=SLOTS,
                             backlog_slots=6, page_size=PAGE)
    assert plan.residency["kvcache"] == "host" and plan.kv_paging is not None
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL, plan=plan,
                      prefill_chunk=CHUNK, params=params)
    assert eng.pool.page_size == plan.kv_paging.page_size
    assert eng.pool.device_pages == plan.kv_paging.device_pages
    results = eng.run(_fresh_requests(reqs))
    for i, r in enumerate(reqs):
        assert np.array_equal(results[r.rid], static_toks[i])
    assert eng.pool.stats["spilled_pages"] > 0


def test_serve_plan_fits_without_pool_when_kv_small():
    cfg = get_config("olmo-1b")
    shape = ShapeConfig("serve", "decode", 256, 4)
    mesh = MeshSpec((1, 1), ("data", "model"))
    plan = plan_serve_memory(cfg, shape, mesh,
                             LMSConfig(hbm_budget=64 * 1024 ** 3),
                             hwlib.TPU_V5E, slots=4)
    assert plan.residency["kvcache"] == "device"
    assert plan.kv_paging is None
    assert plan.fits


def test_price_kv_paging_budget_monotone():
    cfg = get_config("olmo-1b")
    shape = ShapeConfig("serve", "decode", 4096, 16)
    mesh = MeshSpec((1, 1), ("data", "model"))
    small = price_kv_paging(cfg, shape, mesh, budget=4 * 1024 ** 3, slots=16)
    large = price_kv_paging(cfg, shape, mesh, budget=8 * 1024 ** 3, slots=16)
    assert large.device_pages >= small.device_pages
    assert small.page_bytes == large.page_bytes > 0
    assert small.pages_per_slot == -(-4096 // small.page_size)


def test_price_kv_paging_int8_halves_page_bytes():
    """int8 pages (codes + per-row f32 scales) must price well under the
    model-width pages, and the same byte budget must admit ~2x the
    device-resident pages at fixed concurrency demand."""
    cfg = get_config("olmo-1b")
    shape = ShapeConfig("serve", "decode", 4096, 64)
    mesh = MeshSpec((1, 1), ("data", "model"))
    budget = 1 * 1024 ** 3
    full = price_kv_paging(cfg, shape, mesh, budget=budget, slots=64)
    q8 = price_kv_paging(cfg, shape, mesh, budget=budget, slots=64,
                         kv_dtype="int8")
    assert q8.kv_dtype == "int8" and full.kv_dtype == "model"
    ratio = full.page_bytes / q8.page_bytes
    assert 1.5 <= ratio <= 2.0, ratio        # head_dim/(head_dim+4) of 2x
    assert q8.state_bytes == full.state_bytes  # state never quantizes
    # page-budget-bound regime: more pages fit the same bytes
    if full.device_pages < 64 * full.pages_per_slot:
        assert q8.device_pages > full.device_pages


# ---------------------------------------------------------------------------
# int8 KV pages (kv_dtype="int8"): the engine serves the same trace with
# half-width pages in both arenas
# ---------------------------------------------------------------------------

def test_engine_int8_pages_serve_trace(setup):
    cfg, mesh, model, reqs, params, static_toks = setup
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                      page_size=PAGE, prefill_chunk=CHUNK, params=params,
                      kv_dtype="int8")
    assert eng.pool.kv_dtype == "int8"
    # device arena holds int8 codes + f32 per-row scale leaves, and the
    # scale leaves page (spill/return) alongside their codes
    kinds = {keys[-1]: leaf.dtype for keys, leaf in _flat(eng.pool.cache)}
    assert kinds["k"] == jnp.int8 and kinds["v"] == jnp.int8
    assert kinds["k_scale"] == jnp.float32
    assert all(pool_info.paged for keys, pool_info in eng.pool._info.items()
               if keys[-1] in ("k_scale", "v_scale"))
    demand = sum(eng.pool.pages_needed(PROMPT + GEN) for _ in reqs)
    assert demand > eng.pool.device_pages
    results = eng.run(_fresh_requests(reqs))
    assert set(results) == {r.rid for r in reqs}
    st = eng.pool.stats
    assert st["spilled_pages"] > 0
    assert st["fetched_pages"] + st["prefetched_pages"] == st["spilled_pages"]
    # greedy tokens stay within the quantization tolerance of the f32
    # static loop: on this smoke config they match outright
    match = np.mean([np.mean(results[r.rid] == static_toks[i])
                     for i, r in enumerate(reqs)])
    assert match >= 0.9, f"int8 engine diverged from static: match={match}"


def test_quantize_cache_tree_roundtrip(setup):
    """Pool-boundary quantization: dequant(quant(cache)) close to the
    original, rings/state untouched, scale leaves shaped [..., S, K]."""
    from repro.models.kvquant import (dequantize_cache_tree,
                                      quantize_cache_tree)
    cfg, mesh, model, _, _, _ = setup
    rng = np.random.default_rng(2)
    cache = compat.tree.map(
        lambda z: jnp.asarray(rng.standard_normal(z.shape), z.dtype),
        model.init_cache(1, TOTAL))
    qc = quantize_cache_tree(cache, TOTAL)
    names = {keys[-1] for keys, _ in _flat(qc)}
    assert "k_scale" in names and "v_scale" in names
    dq = dequantize_cache_tree(qc)
    for (keys, leaf), (_, orig) in zip(_flat(dq), _flat(cache)):
        np.testing.assert_allclose(np.asarray(leaf), np.asarray(orig),
                                   atol=0.02, rtol=0.02, err_msg=str(keys))


def test_deadline_zero_is_already_expired(setup):
    """Regression pin for the Optional-float truthiness bug class (lint
    RL002, DESIGN.md §11): deadline_s=0.0 is a REAL, already-blown latency
    budget — NOT "no deadline" — and arrival=0.0 is a REAL arrival stamp
    (a trace timed from zero), not "unstamped"."""
    from repro.serve.scheduler import Request
    cfg, mesh, model, _, params, _ = setup
    eng = ServeEngine(model, mesh, slots=SLOTS, max_len=TOTAL,
                      page_size=PAGE, prefill_chunk=CHUNK, params=params)
    prompt = np.arange(4, dtype=np.int32)
    r0 = Request(rid=9901, prompt=prompt, max_new=2,
                 arrival=0.0, deadline_s=0.0)
    assert eng._deadline(r0) == 0.0, \
        "deadline_s=0.0 must resolve to an (expired) deadline, not None"
    r1 = Request(rid=9902, prompt=prompt, max_new=2,
                 arrival=0.0, deadline_s=5.0)
    assert eng._deadline(r1) == 5.0, "arrival=0.0 is a real arrival stamp"
    assert eng._deadline(Request(rid=9903, prompt=prompt, max_new=2,
                                 arrival=0.0, deadline_s=None)) is None
    # and through the lifecycle sweep: the zero-budget request retires as
    # "timeout" at the first scheduling boundary, the 5s one survives
    eng.scheduler.submit(r0)
    eng.scheduler.submit(r1)
    eng._sweep(now=1.0)
    assert r0.status == "timeout"
    assert r1.status == "queued"
