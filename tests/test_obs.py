"""Observability layer (DESIGN.md §12): metrics registry semantics, span
nesting + ring bounds, JSONL sink, spans as profiler host events, spike
detection, and the trainer's spans and log_every flush."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Obs,
                       SpikeDetector, TelemetryAlert, TelemetryLoop,
                       TraceRing, check_site)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _iso_obs(maxlen=8192):
    """Obs with a PRIVATE ring — tests must not touch the global timeline."""
    return Obs(registry=MetricsRegistry(), ring=TraceRing(maxlen=maxlen))


# ---------------------------------------------------------------------------
# registry


def test_counter_gauge_series_semantics():
    reg = MetricsRegistry()
    c = reg.counter("test.hits")
    c.inc()
    c.inc(2.5)
    assert reg.counter("test.hits") is c          # created once
    assert c.value == 3.5
    g = reg.gauge("test.level")
    g.set(7)
    g.set(3)
    assert g.value == 3.0
    s = reg.series("test.rows", maxlen=2)
    s.append({"a": 1})
    s.append({"a": 2})
    s.append({"a": 3})                            # bounded: oldest dropped
    assert [r["a"] for r in s] == [2, 3]
    snap = reg.snapshot()
    assert snap["counters"]["test.hits"] == 3.5
    assert snap["gauges"]["test.level"] == 3.0
    assert snap["series"]["test.rows"] == 2


def test_histogram_window_and_percentiles_match_numpy():
    reg = MetricsRegistry()
    h = reg.histogram("test.lat_s", window=8)
    vals = [0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0]
    for v in vals:
        h.observe(v)
    # cumulative count/total see everything; the window keeps the last 8
    assert h.count == 10 and h.total == sum(vals)
    win = vals[-8:]
    for p in (50, 95, 99):
        assert h.percentile(p) == pytest.approx(np.percentile(win, p))
    s = h.summary()
    assert s["count"] == 10 and s["p50"] == pytest.approx(
        np.percentile(win, 50))


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("test.thing")
    with pytest.raises(TypeError):
        reg.histogram("test.thing")


def test_invalid_site_rejected_everywhere():
    assert check_site("lms.swap_in") == "lms.swap_in"
    with pytest.raises(ValueError):
        check_site("notdotted")
    with pytest.raises(ValueError):
        check_site("Upper.case")
    with pytest.raises(ValueError):
        check_site("unregistered_prefix.x")
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bogus_prefix.count")
    obs = _iso_obs()
    with pytest.raises(ValueError):
        with obs.span("nodots"):
            pass


# ---------------------------------------------------------------------------
# spans, ring, sink


def test_span_nesting_depth_and_exit_recording():
    obs = _iso_obs()
    with obs.span("test.outer", tag="o") as outer:
        assert len(obs.ring) == 0              # spans record on EXIT
        with obs.span("test.inner") as inner:
            inner.attrs.update(extra=1)        # attrs mutable inside
        obs.instant("test.mark")
    evs = obs.ring.events()
    assert [e.site for e in evs] == ["test.inner", "test.mark", "test.outer"]
    assert outer.depth == 0 and inner.depth == 1
    assert evs[1].depth == 1                   # instant inherits live depth
    assert inner.attrs == {"extra": 1}
    assert outer.attrs == {"tag": "o"}
    assert outer.dur >= inner.dur >= 0.0


def test_span_records_on_exception():
    obs = _iso_obs()
    with pytest.raises(RuntimeError):
        with obs.span("test.boom"):
            raise RuntimeError("x")
    assert [e.site for e in obs.ring.events()] == ["test.boom"]


def test_ring_bounded():
    obs = _iso_obs(maxlen=16)
    for _ in range(100):
        obs.instant("test.tick")
    assert len(obs.ring) <= 16


def test_jsonl_sink(tmp_path):
    path = str(tmp_path / "events.jsonl")
    ring = TraceRing(jsonl_path=path)
    obs = Obs(registry=MetricsRegistry(), ring=ring)
    with obs.span("test.a", n=1):
        pass
    obs.instant("test.b")
    ring.set_jsonl(None)                       # close
    rows = [json.loads(line) for line in open(path)]
    assert [r["site"] for r in rows] == ["test.a", "test.b"]
    assert rows[0]["kind"] == "span" and rows[1]["kind"] == "instant"
    assert rows[0]["attrs"] == {"n": 1}


# ---------------------------------------------------------------------------
# spans on the profiler's clock


def _host_events(log_dir):
    """{name: count} of the host events in the profiler trace under
    log_dir."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    counts = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    counts[e.name] = counts.get(e.name, 0) + 1
    return counts


def test_span_is_a_host_event_of_the_profiler_trace(tmp_path):
    import jax
    obs = _iso_obs()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("test.one_clock", step=3):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    # the event is named by the site alone; the attrs stay in the ring
    assert _host_events(str(tmp_path)).get("test.one_clock") == 1
    assert [(e.site, e.attrs) for e in obs.ring.events()] == [
        ("test.one_clock", {"step": 3})]


def test_obs_import_and_spans_do_not_import_jax():
    code = ("import sys\n"
            "from repro.obs import span\n"
            "with span('test.no_jax'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ,
                                  PYTHONPATH=os.path.join(REPO, "src")))
    assert out.returncode == 0, out.stderr


def test_launch_train_trace_writes_a_profiler_trace(tmp_path):
    trace_dir = str(tmp_path / "trace")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "olmo-1b",
         "--smoke", "--steps", "2", "--batch", "2", "--seq", "16",
         "--ckpt-every", "0", "--trace", trace_dir],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.path.join(REPO, "src")))
    assert out.returncode == 0, out.stderr[-2000:]
    host = _host_events(trace_dir)
    assert host.get("train.step") == 2
    for site in ("data.fetch", "train.put", "train.dispatch", "train.sync",
                 "train.flush"):
        assert host.get(site, 0) >= 2, (site, host.get(site))


# ---------------------------------------------------------------------------
# telemetry


def test_spike_detector_fires_on_spike_not_plateau():
    det = SpikeDetector(window=32, factor=6.0, min_delta=0.1, min_steps=8)
    rng = np.random.default_rng(0)
    # a noisy plateau around 1.0 never alerts
    for i in range(50):
        assert det.observe(i, 1.0 + 0.01 * rng.standard_normal()) is None
    alert = det.observe(50, 9.0)
    assert isinstance(alert, TelemetryAlert)
    assert alert.step == 50 and alert.value == 9.0
    assert alert.threshold < 9.0
    d = alert.to_dict()
    assert d["kind"] == "loss_spike" and d["step"] == 50


def test_spike_detector_warmup():
    det = SpikeDetector(min_steps=8)
    for i in range(7):
        assert det.observe(i, 1.0) is None
    # window < min_steps: even a wild value stays silent
    assert det.observe(7, 100.0) is None


def test_telemetry_loop_actions():
    obs = _iso_obs()
    seen = []
    loop = TelemetryLoop(detector=SpikeDetector(min_steps=2, min_delta=0.1),
                         action="stop", on_alert=[seen.append], obs=obs)
    for i in range(5):
        loop.observe(i, {"loss": 1.0})
    assert not loop.stop_requested
    loop.observe(5, {"loss": 50.0})
    assert loop.stop_requested
    assert len(seen) == 1 and len(loop.alerts) == 1
    assert obs.registry.counter("telemetry.alerts").value == 1.0
    assert [e.site for e in obs.ring.events()] == ["telemetry.alert"]

    raising = TelemetryLoop(
        detector=SpikeDetector(min_steps=2, min_delta=0.1), action="raise")
    raising.observe(0, {"loss": 1.0})
    raising.observe(1, {"loss": 1.0})
    with pytest.raises(TelemetryAlert):
        raising.observe(2, {"loss": 50.0})


# ---------------------------------------------------------------------------
# trainer integration: log_every flush + telemetry early-stop


def _tcfg(tmp_path, steps, **kw):
    from repro.config.base import (DDLConfig, LMSConfig, MeshSpec,
                                   ShapeConfig, TrainConfig)
    from repro.configs import get_smoke_config
    return TrainConfig(
        model=get_smoke_config("olmo-1b"),
        shape=ShapeConfig("t", "train", 32, 4),
        mesh=MeshSpec((1, 1), ("data", "model")),
        lms=LMSConfig(enabled=True), ddl=DDLConfig(mode="none"),
        learning_rate=5e-3, warmup_steps=2, total_steps=steps,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=100,
        async_checkpoint=False, **kw)


def test_trainer_log_every_flush_order(tmp_path):
    from repro.train.trainer import Trainer
    obs = _iso_obs()
    tr = Trainer(_tcfg(tmp_path, steps=5, log_every=3), attn_impl="naive",
                 obs=obs)
    seen = []
    _, hist = tr.train(on_step=lambda s, m: seen.append(s))
    # every step logged despite the batched flush, in order
    assert [m["step"] for m in hist] == [1, 2, 3, 4, 5]
    assert seen == [1, 2, 3, 4, 5]
    spans = [e for e in obs.ring.events() if e.site == "train.step"]
    assert len(spans) == 5
    assert len(obs.registry.series("train.history")) == 5
    assert obs.registry.histogram("train.step_s").count == 5


def test_trainer_loop_spans(tmp_path):
    from repro.obs import get_obs, reset
    from repro.train.trainer import Trainer
    reset()
    obs = _iso_obs()
    tr = Trainer(_tcfg(tmp_path, steps=3, log_every=2), attn_impl="naive",
                 obs=obs)
    tr.train()
    # one step: put and dispatch inside train.step, the sync on a flush
    # step only, the flush after the step's span
    assert [(e.site, e.depth) for e in obs.ring.events()] == [
        ("train.put", 1), ("train.dispatch", 1), ("train.step", 0),
        ("train.put", 1), ("train.dispatch", 1), ("train.sync", 1),
        ("train.step", 0), ("train.flush", 0),
        ("train.put", 1), ("train.dispatch", 1), ("train.sync", 1),
        ("train.step", 0), ("train.flush", 0)]
    # the loader makes its rows under data.fetch (one ahead: a prefetch)
    fetches = [e for e in get_obs().ring.events() if e.site == "data.fetch"]
    assert len(fetches) == 4


class _SpikeAt:
    """Stub detector: alerts from a fixed step on."""

    def __init__(self, at):
        self.at = at

    def observe(self, step, value):
        if step >= self.at:
            return TelemetryAlert("loss_spike", step, float(value), 0.0, 0.0)
        return None


def test_trainer_telemetry_early_stop(tmp_path):
    from repro.train.trainer import Trainer
    loop = TelemetryLoop(detector=_SpikeAt(2), action="stop")
    tr = Trainer(_tcfg(tmp_path, steps=8), attn_impl="naive",
                 obs=_iso_obs(), telemetry=loop)
    _, hist = tr.train()
    assert [m["step"] for m in hist] == [1, 2]   # stopped at the alert
    assert loop.alerts and loop.stop_requested
    # the early-stop checkpointed before exiting
    assert tr.ckpt.latest_step() == 2
