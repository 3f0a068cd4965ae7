#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/harness.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell names a configuration (`configs` in BENCHMARK.json -> its file under
benchmarks/chip/configs/) and a traffic mix (benchmarks/chip/traffic/
<name>.json); its correctness limits are in benchmarks/chip/checks/
<cell>.json and each metric is read by benchmarks/chip/metrics/<metric>.py.
A new cell, configuration, mix or metric is a new file; nothing here names
one.

A training run:
  set-up  builds one `Trainer` (the LMS plan, the compiled step, the seeded
          state on the device) and drives it through `Trainer.train` for
          the mix's `checked_steps`, reading the program's numbers on the
          way: the loss of each step, AdamW's first moment after step 1 and
          the master weights after the last checked step;
  window  hands the same trainer and state to `Trainer.train` again, which
          runs closed-loop steps until `--seconds` have passed (the
          benchmark ends the loop through the trainer's telemetry hook);
  check   frees the program's state and runs the float32 reference over
          the checked steps' rows (oracle.py, references/).
With `--trace 1` the window runs under the JAX profiler and the per-layer
metrics are read from the device trace; otherwise the end-to-end ones.

The last line of stdout is one JSON object; the numbers compared with the
reference, each with its limit, are the last lines of stderr and the last
key of that object. Exit code 2 and no JSON when no TPU (or too few chips)
is attached.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import devtrace  # noqa: E402
import feed  # noqa: E402
import flops  # noqa: E402
import hlo  # noqa: E402
import oracle  # noqa: E402

MAX_WINDOW_STEPS = 100_000


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------

def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """BENCHMARK.json's cell `name` with its configuration, traffic mix,
    limits and the names of the metrics it reports."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m["name"] for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m["name"] for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in e2e
                                  else [])]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    return {"name": name, "chips": w["chips"],
            "config": _json(os.path.join(root, conf["file"])),
            "traffic": _json(os.path.join(HERE, "traffic",
                                          w["traffic"] + ".json")),
            "checks": _json(os.path.join(HERE, "checks", name + ".json")),
            "end_to_end": e2e, "per_layer": per_layer, "units": units}


def _module(kind: str, name: str):
    """benchmarks/chip/<kind>/<name>.py as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chip_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _module("metrics", name).read


def reference_module(name: str):
    return _module("references", name)


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

def train_config(cell: dict, seed: int):
    from repro.config.base import (DDLConfig, MeshSpec, ShapeConfig,
                                   TrainConfig)
    from repro.configs import get_config
    conf, traffic = cell["config"], cell["traffic"]
    model = dataclasses.replace(get_config(conf["arch"]), name=conf["name"],
                                **conf["model"])
    opt = traffic["optimizer"]
    mesh = traffic["mesh"]
    return TrainConfig(
        model=model,
        shape=ShapeConfig(cell["name"], "train", traffic["seq_len"],
                          traffic["global_batch"]),
        mesh=MeshSpec((mesh["data"], mesh["model"]), ("data", "model")),
        ddl=DDLConfig(), optimizer=opt["name"],
        learning_rate=opt["learning_rate"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
        beta1=opt["beta1"], beta2=opt["beta2"], grad_clip=opt["grad_clip"],
        microbatches=traffic["microbatches"], seed=seed, checkpoint_every=0,
        log_every=1)


class WindowClock:
    """The trainer's telemetry hook: asks the loop to stop at the first step
    that ends `seconds` after `start()`."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.obs = None
        self.stop_requested = False
        self.t0 = 0.0

    def start(self):
        self.t0 = time.perf_counter()

    def observe(self, step, row):
        if time.perf_counter() - self.t0 >= self.seconds:
            self.stop_requested = True


def _leaf_name(path) -> tuple:
    """State path -> (reference leaf name, stacked?). Decoder stacks are
    decoder/stack0/<layer kind>/<group>/<leaf> with a leading layer axis."""
    keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
    if keys[0] == "decoder":
        return f"layers.{{}}.{keys[-2]}.{keys[-1]}", True
    return ".".join(str(k) for k in keys), False


def _sq_norms(x, stacked: bool):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    x = x.reshape(x.shape[0], -1) if stacked else x.reshape(1, -1)
    return jnp.sum(jnp.square(x), axis=1)


def _sq_norms_diff(x, y, stacked: bool):
    import jax.numpy as jnp
    return _sq_norms(x.astype(jnp.float32) - y.astype(jnp.float32), stacked)


def _on_device(x):
    import jax
    if getattr(x.sharding, "memory_kind", None) == "pinned_host":
        return jax.device_put(x, x.sharding.with_memory_kind("device"))
    return x


def _host_like(tree):
    """Shardings of `tree`'s leaves in pinned host memory, on a TPU."""
    import jax

    def host(x):
        dev = next(iter(x.devices()))
        if dev.platform == "tpu":
            return x.sharding.with_memory_kind("pinned_host")
        return x.sharding
    return jax.tree.map(host, tree)


def leaf_norms(tree, minus=None, scale: float = 1.0) -> dict:
    """{leaf name: norm} of a state tree (of tree - minus), stacked leaves
    read layer by layer, each leaf brought to the device on its own."""
    import jax
    import numpy as np
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    others = (jax.tree.leaves(minus) if minus is not None
              else [None] * len(flat))
    out = {}
    for (path, x), y in zip(flat, others):
        name, stacked = _leaf_name(path)
        xd = _on_device(x)
        if y is None:
            sq = jax.jit(_sq_norms, static_argnums=1)(xd, stacked)
        else:
            yd = _on_device(y)
            sq = jax.jit(_sq_norms_diff, static_argnums=2)(xd, yd, stacked)
            del yd
        norms = np.sqrt(np.asarray(sq, np.float64)) * scale
        del xd
        if stacked:
            out.update({name.format(i): float(v) for i, v in enumerate(norms)})
        else:
            out[name] = float(norms[0])
    return out


def set_up(cell: dict, seed: int, step_wrapper=None):
    """-> (trainer, {"state", "start"}: the state after the checked steps
    and the step the window starts from, the program's readings)."""
    import jax
    from repro.train.trainer import Trainer
    tcfg = train_config(cell, seed)
    trainer = Trainer(tcfg)
    trainer.loader.source = feed.make_source(cell["traffic"],
                                             tcfg.model.vocab_size, seed)
    if step_wrapper is not None:
        trainer.step_fn = step_wrapper(trainer.step_fn)
    held = {"state": trainer.init_state(), "start": 0}
    trainer.resume_or_init = lambda: (held["state"], held["start"])
    n = cell["traffic"]["checked_steps"]
    state, rows = trainer.train(steps=1)
    readings = {"grad": leaf_norms(state.opt.mu, scale=1.0 / (1 - tcfg.beta1))}
    held.update(state=state, start=1)
    state, more = trainer.train(steps=n)
    rows += more
    # the seed's starting weights, made again by the program's own init
    # into host memory where there is one, so that reading them adds one
    # leaf at a time to the device
    start = jax.jit(trainer.model.init, out_shardings=_host_like(
        state.params))(jax.random.key(seed))
    readings["update"] = leaf_norms(state.opt.master, minus=start)
    del start
    readings["loss"] = [r["loss"] for r in rows]
    held.update(state=state, start=n)
    return trainer, held, readings


def checked_batches(cell: dict, seed: int) -> list:
    t = cell["traffic"]
    src = feed.make_source(t, cell["config"]["model"]["vocab_size"], seed)
    return [src.batch(i, 0, 1, t["global_batch"], t["seq_len"])
            for i in range(t["checked_steps"])]


def reference_readings(cell: dict, seed: int, variant: str = "f32") -> dict:
    import jax
    ref = reference_module(cell["config"]["reference"])
    with jax.default_matmul_precision("highest"):
        return ref.Reference(cell["config"]["model"],
                             cell["traffic"]["optimizer"], seed,
                             variant).run(checked_batches(cell, seed))


def _annotate(trainer):
    """Name the host's work in the trace: the feed, the batch's transfer,
    the step's dispatch."""
    import jax
    def wrap(fn, name):
        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return inner
    src = trainer.loader.source
    src.batch = wrap(src.batch, "bench.feed")
    trainer._make_batch = wrap(trainer._make_batch, "bench.make_batch")
    trainer.step_fn = wrap(trainer.step_fn, "bench.step_call")


def device_peak(dev):
    """The peaks table's row for the attached chip; None off TPU. A TPU
    kind the table lacks is an error."""
    if dev.platform != "tpu":
        return None
    table = _json(os.path.join(HERE, "peaks.json"))
    if dev.device_kind not in table:
        raise KeyError(f"no peaks for device_kind {dev.device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[dev.device_kind]


def device_peak_bytes(devices, log=print) -> int:
    """Peak on the fullest chip: buffers (peak_bytes_in_use) plus the
    region compiled programs keep their temporaries in
    (peak_bytes_reserved)."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        log(f"memory_stats of {d}: {s}")
        peak = max(peak, s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0))
    return int(peak)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run(cell: dict, seed: int, seconds: float, trace_on: bool, *,
        require_tpu: bool = True, compile_cache: bool = True,
        step_wrapper=None, log=print):
    """-> (result dict for the JSON line, exit code)."""
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        log(f"FAIL: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX sees {len(devs)} {devs[0].platform} device(s)")
        return None, 2
    if compile_cache:
        from repro.launch.cache import configure_compile_cache
        log(f"compile cache: {configure_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = {"armed": False, "n": 0}

    def on_event(event, secs, **_):
        if compiles["armed"] and event.endswith("backend_compile_duration"):
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    traffic = cell["traffic"]
    trainer, held, prog = set_up(cell, seed, step_wrapper)
    log(trainer.plan.summary() if trainer.plan is not None else "no LMS plan")
    clock = WindowClock(seconds)
    trainer.telemetry = clock
    trace_dir = None
    if trace_on:
        _annotate(trainer)
        trace_dir = tempfile.mkdtemp(prefix="chip_trace_")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=devtrace.options())
    setup_s = time.perf_counter() - T_PROCESS
    compiles["armed"] = True
    clock.start()
    state, rows = trainer.train(steps=held["start"] + MAX_WINDOW_STEPS)
    jax.block_until_ready(state)
    window_s = time.perf_counter() - clock.t0
    compiles["armed"] = False
    if trace_on:
        jax.profiler.stop_trace()
    devices = list(trainer.mesh.devices.flat)
    peak = device_peak_bytes(devices, log)
    losses = [r["loss"] for r in rows]
    failed = sum(1 for x in losses if not math.isfinite(x))
    tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
    ctx = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, steps=len(rows),
        tokens_per_step=tokens_per_step, chips=cell["chips"],
        flops_per_token=flops.flops_per_token(cell["config"]["model"],
                                              traffic["seq_len"]),
        peak=device_peak(devs[0]), trace=None)
    del state, held, trainer
    gc.collect()
    log(f"window: {len(rows)} steps in {window_s:.3f} s; compiles inside "
        f"the window: {compiles['n']}; device peak {peak} B")

    t_ref = time.perf_counter()
    try:
        ref = reference_readings(cell, seed)
    except Exception as e:  # the check could not be made: not correct
        log(f"reference failed: {type(e).__name__}: {e}")
        ref = {"loss": [], "grad": {}, "update": {}}
    log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    numbers = oracle.compare(prog, ref)
    limits = cell["checks"]["limits"]
    correct = (len(rows) > 0 and failed == 0
               and all(numbers[k][0] <= limits[k] for k in oracle.NUMBERS))
    log(f"program losses {prog['loss']}; reference {ref['loss']}")

    if trace_on:
        ctx.trace = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    names = cell["per_layer"] if trace_on else cell["end_to_end"]
    metrics = {}
    for name in names:
        value = metric_reader(name)(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell["units"][name]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(rows),
              "failed": failed, "metrics": metrics, "device": device}
    if trace_on:
        busy, traced_s, breakdown = _trace_summary(ctx)
        device.update(busy_s=busy, window_s=traced_s)
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": numbers[k][0], "limit": limits[k]}
                        for k in oracle.NUMBERS}
    for k in oracle.NUMBERS:
        note = f" (worst leaf {numbers[k][1]})" if numbers[k][1] else ""
        log(f"check {k} {numbers[k][0]!r} limit {limits[k]!r}{note}")
    return result, 0


def _trace_summary(ctx):
    """-> (busy_s averaged over the chips, traced window s, breakdown)."""
    tr = devtrace
    t = ctx.trace
    planes = sorted(t["devices"])
    if not planes:
        return 0.0, ctx.window_s, {"device_ops": [], "idle_gaps": []}
    busy = [tr.union_ns((s, e) for _, s, e in t["devices"][p]["ops"]) / 1e9
            for p in planes]
    ops = t["devices"][planes[0]]["ops"]
    per_op = {}
    for name, s, e in ops:
        short = hlo.short_name(name)
        per_op[short] = per_op.get(short, 0.0) + (e - s) / 1e9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    module = tr.step_module(t)
    runs = tr.step_runs(t, planes[0], module) if module else []
    gaps = []
    if runs:
        lo, hi = runs[0][0], runs[-1][1]
        for s, e in tr.idle_gaps([(s, e) for _, s, e in ops], lo, hi):
            gaps.append((tr.host_label(t, s, e), (e - s) / 1e9))
    gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return (sum(busy) / len(busy), ctx.window_s,
            {"device_ops": [[n, v] for n, v in top],
             "idle_gaps": [[n, v] for n, v in gaps]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    err = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    result, code = run(cell, args.seed, args.seconds, bool(args.trace),
                       log=err)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
