"""Static-analysis subsystem (DESIGN.md §11): synthetic jaxpr fixtures
asserting each finding code fires exactly where designed (and nowhere
else), the repo-wide lint gate, the recompile sentinel, and end-to-end
audits over the real slot-decode builders asserting zero findings."""
import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import audit_step, aval_fingerprint
from repro.analysis.lint import default_paths, lint_paths, lint_source
from repro.analysis.report import AnalysisReport, Finding, StepAudit

S = jax.ShapeDtypeStruct
DEV = jax.devices()[0]


def codes(audit):
    return sorted(f.code for f in audit.findings)


# ---------------------------------------------------------------------------
# synthetic jaxpr fixtures — one per finding code


def test_dropped_donation_fires_jxa001():
    """Donated input whose aval no output can consume: XLA silently drops
    the donation; the auditor must not."""
    f = jax.jit(lambda x: x.sum(), donate_argnums=(0,))
    a = audit_step("drop", f, (S((8, 8), jnp.float32),),
                   expect_donation=True)
    assert codes(a) == ["JXA001"]
    assert a.donated_in == 1 and a.donated_aliased == 0


def test_transfer_in_scan_fires_jxa003():
    def body(c, _):
        return jax.device_put(c, DEV) + 1.0, None

    f = jax.jit(lambda x: jax.lax.scan(body, x, None, length=4)[0])
    a = audit_step("scan", f, (S((8,), jnp.float32),))
    assert codes(a) == ["JXA003"]
    # the SAME transfer is legitimate when the plan's schedule streams —
    # per-layer device_puts inside the layer scan ARE the executor then
    a2 = audit_step("scan", f, (S((8,), jnp.float32),),
                    allow_scan_transfers=True)
    assert codes(a2) == []


def test_int8_upcast_fires_jxa004():
    kv = S((4, 4), jnp.int8)
    f = jax.jit(lambda k: k.astype(jnp.float32).sum())
    a = audit_step("up", f, (kv,), tracked_quant_avals=[kv])
    assert codes(a) == ["JXA004"]
    # per-slice dequantize produces a DIFFERENT aval than the whole leaf
    # and must not be flagged (that's how int8 decode reads pages)
    g = jax.jit(lambda k: k[0].astype(jnp.float32).sum())
    assert codes(audit_step("slice", g, (kv,),
                            tracked_quant_avals=[kv])) == []
    # allowlisted leaves are exempt
    assert codes(audit_step("allow", f, (kv,), tracked_quant_avals=[kv],
                            allow_upcast=[kv])) == []


def test_clean_fn_has_no_findings():
    f = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
    a = audit_step("clean", f, (S((8, 8), jnp.float32),),
                   expect_donation=True)
    assert codes(a) == []
    assert a.donated_in == 1 and a.donated_aliased == 1


def test_host_leaf_on_device_fires_jxa002():
    aval = S((16,), jnp.float32)
    f = jax.jit(lambda x: jax.device_put(x, DEV) * 1.0)
    a = audit_step("host", f, (aval,), host_avals=[aval])
    assert codes(a) == ["JXA002"]
    # leaves the plan does NOT declare host are free to move
    assert codes(audit_step("ok", f, (aval,),
                            host_avals=[S((32,), jnp.float32)])) == []


def test_peak_estimate_and_budget_warning_jxa005():
    aval = S((16, 16), jnp.float32)
    f = jax.jit(lambda x: (x @ x).sum())
    a = audit_step("peak", f, (aval,), budget_bytes=8)
    assert "JXA005" in codes(a)
    jxa5 = [x for x in a.findings if x.code == "JXA005"]
    assert all(x.severity == "warning" for x in jxa5)
    assert not [x for x in a.findings if x.gating], \
        "the budget reconciliation is advisory, not a gate"
    assert a.peak_live_bytes >= 16 * 16 * 4  # at least the input stays live


# ---------------------------------------------------------------------------
# lint rules — synthetic sources


def _codes(src, path="pkg/mod.py", waived=None):
    fs = lint_source(textwrap.dedent(src), path)
    if waived is not None:
        fs = [f for f in fs if f.waived == waived]
    return [f.code for f in fs]


def test_rl001_time_time():
    assert _codes("import time\nt = time.time()\n") == ["RL001"]
    assert _codes("import time\nt = time.monotonic()\n") == []


def test_rl002_optional_truthiness():
    assert _codes("if req.deadline_s:\n    pass\n") == ["RL002"]
    assert _codes("x = 1 if not r.arrival else 2\n") == ["RL002"]
    assert _codes("if req.deadline_s is not None:\n    pass\n") == []
    assert _codes("if req.deadline_s is None or now > dl:\n    pass\n") == []


def test_rl003_kv_dtype_compare():
    assert _codes('if kv_dtype == "int8":\n    pass\n') == ["RL003"]
    assert _codes('if self.kv_dtype != "model":\n    pass\n') == ["RL003"]
    assert _codes('if kvquant.validate_kv_dtype(kv_dtype) == "int8":\n'
                  "    pass\n") == []
    assert _codes("if kvquant.is_int8(kv_dtype):\n    pass\n") == []


def test_rl007_obs_site_names():
    # well-formed site under a registered prefix: clean
    assert _codes('obs.span("lms.swap_in", bytes=4)\n') == []
    assert _codes('reg.counter("engine.ticks").inc()\n') == []
    # typo'd / unregistered prefix
    assert _codes('obs.span("lmss.swap_in")\n') == ["RL007"]
    assert _codes('obs.instant("engin.preempt")\n') == ["RL007"]
    # not a lowercase dotted identifier
    assert _codes('obs.span("swapin")\n') == ["RL007"]
    assert _codes('obs.span("LMS.SwapIn")\n') == ["RL007"]
    # dynamic names are runtime-checked, not lint territory
    assert _codes('obs.span(f"{site}_bytes.{cls}")\n') == []
    assert _codes("obs.span(name)\n") == []
    # waiver works like every other rule
    assert _codes('obs.span("weird.site")  '
                  "# lint: waive RL007 external namespace\n",
                  waived=False) == []


def test_rl007_named_scope_sites():
    # a named scope is a device-side site: registered and dotted, or flagged
    assert _codes('with jax.named_scope("train.layers"):\n    pass\n') == []
    assert _codes('with jax.named_scope("lms.opt_sweep"):\n    pass\n') == []
    assert _codes('with jax.named_scope("layers"):\n    pass\n') == ["RL007"]
    assert _codes('with jax.named_scope("model.layers"):\n    pass\n') \
        == ["RL007"]
    assert _codes("with jax.named_scope(name):\n    pass\n") == []


def test_rl004_tracer_host_pull_scoped_to_hot_paths():
    src = "def _tick(self):\n    rows = np.asarray(logits)\n"
    assert _codes(src, path="serve/engine.py") == ["RL004"]
    assert _codes(src, path="serve/other.py") == []
    assert _codes("def helper(self):\n    rows = np.asarray(x)\n",
                  path="serve/engine.py") == []


def test_rl005_bench_timing_needs_block():
    src = """
    import time
    def bench():
        t0 = time.monotonic()
        work()
        return time.monotonic() - t0
    """
    path = "/repo/benchmarks/bench_x.py"
    assert _codes(src, path=path) == ["RL005"]
    blocked = src.replace("work()", "jax.block_until_ready(work())")
    assert _codes(blocked, path=path) == []
    assert _codes(src, path="/repo/src/x.py") == []  # bench-only rule


def test_rl006_unclamped_index_map():
    src = """
    spec = pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, grid=(4,))
    bad = pl.BlockSpec(index_map=lambda i, kvl: (kvl, 0),
                       block_shape=(8, 8))
    """
    assert _codes(src, path="x/kernels/k.py") == ["RL006"]
    good = src.replace("(kvl, 0)", "(jnp.minimum(kvl, 3), 0)")
    assert _codes(good, path="x/kernels/k.py") == []
    # index_maps that ignore the prefetch ref are fine
    qmap = src.replace("(kvl, 0)", "(i, 0)")
    assert _codes(qmap, path="x/kernels/k.py") == []
    # delegation to a local clamped helper is fine (scale_block pattern)
    deleg = """
    spec = pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, grid=(4,))
    def kv_map(i, kvl):
        return (jnp.minimum(kvl, 3), 0)
    def scale_map(i, kvl):
        return kv_map(i, kvl)
    a = pl.BlockSpec(index_map=kv_map, block_shape=(8, 8))
    b = pl.BlockSpec(index_map=scale_map, block_shape=(8,))
    """
    assert _codes(deleg, path="x/kernels/k.py") == []


def test_waiver_syntax_suppresses_gating_not_reporting():
    src = ("import time\n"
           "t = time.time()  # lint: waive RL001 wall-clock by design\n")
    fs = lint_source(src, "pkg/mod.py")
    assert [f.code for f in fs] == ["RL001"]
    assert fs[0].waived and not fs[0].gating
    assert fs[0].waiver_reason == "wall-clock by design"
    # line-above form
    src2 = ("import time\n"
            "# lint: waive RL001 wall-clock by design\n"
            "t = time.time()\n")
    fs2 = lint_source(src2, "pkg/mod.py")
    assert fs2[0].waived
    # a waiver for a DIFFERENT code does not suppress
    src3 = ("import time\n"
            "t = time.time()  # lint: waive RL002 wrong code\n")
    assert not lint_source(src3, "pkg/mod.py")[0].waived


def test_repo_lint_zero_unwaived_findings():
    """THE repo gate: src/repro + benchmarks lint clean (waivers allowed,
    unwaived findings are failures) — same pass scripts/ci.sh runs."""
    root, roots = default_paths()
    findings = lint_paths(roots, root)
    gating = [f for f in findings if f.gating]
    assert not gating, "unwaived lint findings:\n" + "\n".join(
        f"  {f.code} {f.where}: {f.message}" for f in gating)


# ---------------------------------------------------------------------------
# report plumbing


def test_report_json_roundtrip(tmp_path):
    rep = AnalysisReport(
        steps=[StepAudit(name="s", findings=[
            Finding("JXA005", "over", "s", severity="warning")],
            peak_live_bytes=100, plan_peak_bytes=60)],
        lint=[Finding("RL001", "m", "f.py:1", waived=True,
                      waiver_reason="why")])
    assert rep.ok  # warning + waived -> nothing gates
    p = tmp_path / "analysis_report.json"
    rep.write(str(p))
    d = json.loads(p.read_text())
    assert d["ok"] and d["n_findings"] == 2 and d["n_gating"] == 0
    assert d["steps"][0]["plan_delta_bytes"] == 40
    rep.lint.append(Finding("RL001", "m", "f.py:2"))
    assert not rep.ok


# ---------------------------------------------------------------------------
# end-to-end over the real builders


@pytest.fixture(scope="module")
def smoke_env():
    from repro.config.base import MeshSpec
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model
    cfg = get_smoke_config("olmo-1b")
    mspec = MeshSpec((1, 1), ("data", "model"))
    mesh = make_mesh(mspec)
    return cfg, mspec, mesh, Model(cfg, attn_impl="naive")


@pytest.mark.parametrize("kv_dtype,use_arena",
                         [("model", False), ("int8", False), ("int8", True)])
def test_slot_decode_audit_zero_findings(smoke_env, kv_dtype, use_arena):
    """The real serve hot path conforms to its plan: donation aliased,
    no loop transfers outside the stream, no whole-leaf int8 upcasts."""
    from repro.analysis.run import slot_decode_builder
    cfg, mspec, mesh, model = smoke_env
    fn, args, plan, cache = slot_decode_builder(
        model, cfg, mspec, mesh, slots=2, max_len=16, page=4,
        kv_dtype=kv_dtype, use_arena=use_arena)
    tracked = [l for l in jax.tree_util.tree_leaves(cache)
               if str(l.dtype) == "int8"]
    if kv_dtype == "int8":
        assert tracked, "int8 variant must actually track int8 leaves"
    a = audit_step("slot_decode", fn, args, expect_donation=True,
                   tracked_quant_avals=tracked, allow_scan_transfers=True,
                   plan_peak_bytes=plan.peak_bytes)
    assert codes(a) == [], [f.message for f in a.findings]
    assert a.donated_in > 0 and a.donated_aliased == a.donated_in


def test_recompile_sentinel_one_signature_across_churn(smoke_env):
    """Every churn scenario (idle, join, full, stagger, evict) produces
    the SAME step signature; genuinely different shapes produce another."""
    from repro.analysis.run import sentinel_fingerprints
    fps = sentinel_fingerprints("olmo-1b", slots=2, max_len=16)
    assert len(fps) >= 4
    assert len(set(fps.values())) == 1, fps
    fps3 = sentinel_fingerprints("olmo-1b", slots=3, max_len=16)
    assert set(fps3.values()) != set(fps.values()), \
        "a real shape change must change the signature"


def test_schedule_invariant_audits_concrete_step():
    """check_schedule_invariant(step_fn=...) is the single entry point for
    plan-time + compile-time conformance."""
    from repro.core.lms.planner import check_schedule_invariant
    bad = jax.jit(lambda x: x.sum(), donate_argnums=(0,))
    with pytest.raises(AssertionError, match="JXA001"):
        check_schedule_invariant({}, None, step_fn=bad,
                                 step_args=(S((4,), jnp.float32),),
                                 expect_donation=True)
    good = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
    check_schedule_invariant({}, None, step_fn=good,
                             step_args=(S((4,), jnp.float32),),
                             expect_donation=True)


def test_fingerprint_covers_dtype_and_treedef():
    a = aval_fingerprint({"x": S((4,), jnp.int32)}, static=(1,))
    assert a == aval_fingerprint({"x": S((4,), jnp.int32)}, static=(1,))
    assert a != aval_fingerprint({"x": S((4,), jnp.int8)}, static=(1,))
    assert a != aval_fingerprint({"y": S((4,), jnp.int32)}, static=(1,))
    assert a != aval_fingerprint({"x": S((4,), jnp.int32)}, static=(2,))
