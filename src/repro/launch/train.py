"""End-to-end training driver (the `ddlrun` analogue).

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
        --steps 200 --batch 8 --seq 128 --mesh 1x1 --ddl-mode allreduce

On the CPU container this trains reduced configs; on a pod the same driver
takes --mesh 16x16 / --mesh 2x16x16 and the production arch ids.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.config.base import (DDLConfig, LMSConfig, MeshSpec, ShapeConfig,
                               TrainConfig)
from repro.configs import get_config, get_smoke_config
from repro.launch.cache import configure_compile_cache
from repro.obs import TelemetryLoop, configure, get_obs, profiler_trace
from repro.runtime import (FaultEvent, FaultInjector, FaultPlan,
                           RestartPolicy, Supervisor)
from repro.train.trainer import Trainer


def parse_mesh(s: str) -> MeshSpec:
    dims = tuple(int(x) for x in s.split("x"))
    if len(dims) == 3:
        return MeshSpec(dims, ("pod", "data", "model"))
    if len(dims) == 2:
        return MeshSpec(dims, ("data", "model"))
    return MeshSpec(dims, ("data",))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced smoke config")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--mesh", default="1x1")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--ddl-mode", default="allreduce",
                   choices=["allreduce", "zero1", "none"])
    p.add_argument("--compress-dcn", action="store_true")
    p.add_argument("--no-lms", action="store_true")
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50,
                   help="checkpoint every N steps; 0 saves and resumes "
                        "nothing")
    p.add_argument("--log", default="")
    # observability (DESIGN.md §12)
    p.add_argument("--log-every", type=int, default=1,
                   help="flush device metrics to host every N steps (the "
                        "per-step float() sync becomes every-N)")
    p.add_argument("--obs-jsonl", default="",
                   help="stream span events to this JSONL file as they "
                        "are recorded")
    p.add_argument("--trace", default="",
                   help="run under the JAX profiler, writing its trace "
                        "(host spans and device ops on one clock) under "
                        "this directory")
    p.add_argument("--spike-action", default="off",
                   choices=["off", "record", "stop"],
                   help="loss-spike telemetry: record alerts, or stop the "
                        "run early on a spike")
    # supervised mode: crash-recovery loop (restore -> reshard -> resume)
    p.add_argument("--supervise", action="store_true",
                   help="run under the Supervisor: on failure, restore the "
                        "last committed checkpoint, reshard onto surviving "
                        "devices, and resume")
    p.add_argument("--heartbeat-dir", default="",
                   help="heartbeat store directory (enables liveness beats)")
    p.add_argument("--max-restarts", type=int, default=10)
    p.add_argument("--fault-step", type=int, default=-1,
                   help="drill: inject a fatal fault before this 0-based "
                        "step (requires --supervise to survive it)")
    p.add_argument("--lost-devices", type=int, default=0,
                   help="drill: devices the injected fault takes down "
                        "(triggers an elastic reshard on restart)")
    p.add_argument("--fault-seed", type=int, default=-1,
                   help="drill: sample a random FaultPlan from this seed "
                        "(REPRO_FAULT_SEED also works) instead of "
                        "--fault-step")
    args = p.parse_args(argv)

    configure_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    tcfg = TrainConfig(
        model=cfg,
        shape=ShapeConfig("cli", "train", args.seq, args.batch),
        mesh=parse_mesh(args.mesh),
        lms=LMSConfig(enabled=not args.no_lms),
        ddl=DDLConfig(mode=args.ddl_mode, compress_dcn=args.compress_dcn),
        learning_rate=args.lr, warmup_steps=args.warmup,
        total_steps=args.steps, microbatches=args.microbatches,
        checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
        log_every=max(1, args.log_every))

    configure(jsonl_path=args.obs_jsonl or None)
    obs = get_obs()
    telemetry = (TelemetryLoop(action=args.spike_action, obs=obs)
                 if args.spike_action != "off" else None)

    def log(step, m):
        print(f"step {step:5d} | loss {m['loss']:.4f} | gnorm "
              f"{m['grad_norm']:.3f} | lr {m['lr']:.2e} | {m['time_s']*1e3:.0f} ms")

    injector = None
    if args.fault_step >= 0:
        payload = ({"lost_devices": args.lost_devices}
                   if args.lost_devices else {})
        injector = FaultInjector(FaultPlan(
            [FaultEvent("trainer.step", at=args.fault_step,
                        payload=payload)]))
    elif args.fault_seed >= 0:
        injector = FaultInjector(FaultPlan.sample(
            args.fault_seed, sites=("trainer.step", "ckpt.commit")))

    with profiler_trace(args.trace):
        if args.supervise:
            sup = Supervisor(tcfg,
                             heartbeat_dir=args.heartbeat_dir or None,
                             policy=RestartPolicy(
                                 max_restarts=args.max_restarts,
                                 backoff_base=0.01, max_delay=1.0),
                             injector=injector, obs=obs, telemetry=telemetry)
            res = sup.run(steps=args.steps, on_step=log)
            state, hist = res.state, res.hist
            for note in res.notes:
                print(f"reshard: {note}")
            if res.restarts:
                print(f"recovered from {res.restarts} failure(s) "
                      f"in {res.attempts} attempts")
        else:
            trainer = Trainer(tcfg, heartbeat_dir=args.heartbeat_dir or None,
                              injector=injector, obs=obs,
                              telemetry=telemetry)
            state, hist = trainer.train(steps=args.steps, on_step=log)
    if args.trace:
        print(f"profiler trace: {args.trace}")
    if args.log:
        with open(args.log, "w") as f:
            json.dump(hist, f, indent=1)
    if telemetry is not None and telemetry.alerts:
        for a in telemetry.alerts:
            print(f"telemetry alert: {a}")
    print(f"final loss: {hist[-1]['loss']:.4f} (from {hist[0]['loss']:.4f})")
    print("-- metrics --")
    for line in obs.registry.summary_lines():
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
