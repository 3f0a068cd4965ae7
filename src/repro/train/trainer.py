"""Training loop: LMS-planned, DDL-reduced steps + async checkpointing,
heartbeats, straggler stats, and crash-restart (resume from the latest
committed checkpoint, including the data-iterator position).

A `FaultInjector` (repro.runtime.inject) threads through the loop for the
crash-recovery drills: site ``trainer.step`` fires before each step
dispatch (the simulated lost-peer / XLA abort the Supervisor catches),
``heartbeat`` can drop or tear the per-step beat (what the
FailureDetector sees from a dying process), and the injector passes into
the Checkpointer for the mid-save crash windows. All hooks are no-ops
when no injector is installed.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.config.base import TrainConfig
from repro.core.lms.planner import PlanRequest, plan as plan_lms
from repro.data import DataLoader, SyntheticTokens, make_vlm_batch, make_audio_batch
from repro.launch.mesh import make_mesh, mesh_axis_sizes
from repro.models.model import Model
from repro.obs import Obs, TelemetryLoop
from repro.runtime import HeartbeatStore, StepTimer
from repro.runtime import inject
from repro.train.steps import (build_train_step, init_train_state,
                               build_zero1_train_step, init_zero1_state,
                               TrainState)


class Trainer:
    def __init__(self, tcfg: TrainConfig, *, attn_impl: str = "blockwise",
                 process: int = 0, heartbeat_dir: Optional[str] = None,
                 injector=None, obs: Optional[Obs] = None,
                 telemetry: Optional[TelemetryLoop] = None):
        self.tcfg = tcfg
        # private registry over the shared span ring (same pattern as the
        # serve engine); a supplied telemetry loop records its alerts here
        self.obs = obs if obs is not None else Obs()
        self.telemetry = telemetry
        if telemetry is not None and telemetry.obs is None:
            telemetry.obs = self.obs
        self.mesh = make_mesh(tcfg.mesh)
        self.model = Model(tcfg.model, attn_impl=attn_impl)
        self.plan = (plan_lms(PlanRequest(
                        cfg=tcfg.model, shape=tcfg.shape, mesh=tcfg.mesh,
                        lms=tcfg.lms, optimizer=tcfg.optimizer,
                        zero1=(tcfg.ddl.mode == "zero1"),
                        microbatches=tcfg.microbatches))
                     if tcfg.lms.enabled else None)
        self.process = process
        self._inj = injector
        # checkpoint_every=0 turns checkpointing off: nothing is saved,
        # nothing resumed, and the checkpoint directory is never touched
        self.ckpt = (Checkpointer(tcfg.checkpoint_dir,
                                  async_save=tcfg.async_checkpoint,
                                  injector=injector)
                     if tcfg.checkpoint_every > 0 else None)
        self.hb = HeartbeatStore(heartbeat_dir) if heartbeat_dir else None
        self.timer = StepTimer()
        sizes = mesh_axis_sizes(self.mesh)
        self.dp = sizes.get("data", 1) * sizes.get("pod", 1)
        self.zero1 = tcfg.ddl.mode == "zero1"
        if self.zero1:
            (self.step_fn, self.state_sh, self.batch_sh,
             self._packspec) = build_zero1_train_step(
                self.model, tcfg, self.mesh, plan=self.plan)
        else:
            self.step_fn, self.state_sh, self.batch_sh = build_train_step(
                self.model, tcfg, self.mesh, plan=self.plan)
        self.loader = DataLoader(
            SyntheticTokens(tcfg.model.vocab_size, seed=tcfg.seed),
            shard=process, num_shards=1,
            batch_per_shard=tcfg.shape.global_batch,
            seq_len=tcfg.shape.seq_len)

    # ---- state ---------------------------------------------------------
    def init_state(self):
        """Build the state under jit, straight into the residency shardings:
        host-resident classes (the optimizer state of a beyond-HBM plan)
        are written to pinned host memory as they are made, so the whole
        state never exists on the device."""
        rng = jax.random.key(self.tcfg.seed)
        if self.zero1:
            sizes = mesh_axis_sizes(self.mesh)
            init = functools.partial(init_zero1_state, self.model, self.tcfg,
                                     data_size=sizes.get("data", 1))
        else:
            init = functools.partial(init_train_state, self.model, self.tcfg)
        return jax.jit(init, out_shardings=self.state_sh)(rng)

    def resume_or_init(self):
        latest = self.ckpt.latest_step() if self.ckpt is not None else None
        if latest is None:
            return self.init_state(), 0
        _, state_np, extra = self.ckpt.restore(latest)
        # host arrays go straight to their residency (pinned host for
        # host-resident classes), never through the default device
        state = jax.device_put(self._rebuild_state(state_np), self.state_sh)
        if extra.get("data_state"):
            self.loader.restore(extra["data_state"])
        return state, latest

    def _rebuild_state(self, d):
        """npz roundtrip flattens NamedTuples to dicts; rebuild them."""
        from repro.optim.adamw import AdamState, SGDState
        from repro.train.steps import Zero1State
        step = np.asarray(d["step"])
        if self.zero1:
            return Zero1State(step, d["params"], np.asarray(d["mu"]),
                              np.asarray(d["nu"]), np.asarray(d["master"]))
        o = d["opt"]
        if self.tcfg.optimizer == "adamw":
            opt = AdamState(np.asarray(o["step"]), o["mu"], o["nu"], o["master"])
        else:
            opt = SGDState(np.asarray(o["step"]), o["momentum"])
        bias = d.get("router_bias")
        return TrainState(step, d["params"], opt,
                          None if bias is None else np.asarray(bias))

    def _make_batch(self) -> Dict[str, jnp.ndarray]:
        cfg = self.tcfg.model
        b, s = self.tcfg.shape.global_batch, self.tcfg.shape.seq_len
        rng = np.random.default_rng(self.loader.global_step)
        # the host makes the rows; the train.put span covers their
        # conversion and transfer to the batch's shardings
        bf16 = ()
        if cfg.family == "vlm":
            raw = make_vlm_batch(rng, b, s, cfg.d_model, cfg.vocab_size)
            raw["embeds"] = raw["embeds"].astype(np.float32)
            bf16 = ("embeds",)
            self.loader.state.step_in_epoch += 1
        elif cfg.family == "audio":
            raw = make_audio_batch(rng, b, s, cfg.encoder_seq, cfg.d_model,
                                   cfg.vocab_size)
            bf16 = ("enc_embeds",)
            self.loader.state.step_in_epoch += 1
        else:
            raw = next(self.loader)
        with self.obs.span("train.put"):
            batch = {k: jnp.asarray(v, jnp.bfloat16 if k in bf16 else None)
                     for k, v in raw.items()}
            return jax.device_put(batch, self.batch_sh)

    # ---- loop ----------------------------------------------------------
    def train(self, steps: Optional[int] = None,
              on_step: Optional[Callable] = None):
        state, start = self.resume_or_init()
        steps = steps or self.tcfg.total_steps
        log_every = max(1, self.tcfg.log_every)
        series = self.obs.registry.series("train.history")
        step_hist = self.obs.registry.histogram("train.step_s")
        metrics_hist: list = []
        pending: list = []
        stop = False

        def _flush():
            # THE deferred host sync (DESIGN.md §12): metrics stay on device
            # until here, so with log_every > 1 the float() pulls — and the
            # dispatch stall they imply — amortize over log_every steps.
            # on_step / telemetry fire at flush, in step order.
            nonlocal stop
            for step, metrics, dt in pending:
                row = {"step": step, "loss": float(metrics["loss"]),
                       "grad_norm": float(metrics["grad_norm"]),
                       "lr": float(metrics["lr"]), "time_s": dt,
                       # model aux metrics (real, not fabricated):
                       # ce = cross-entropy, aux = MoE balance loss
                       "ce": float(metrics["ce"]),
                       "aux": float(metrics["aux"])}
                if "moe_held_rows" in metrics:
                    # rows routed to the held experts this step, and the
                    # chunks they ran in (one a layer unless one overflowed)
                    row["moe_held_rows"] = float(metrics["moe_held_rows"])
                    row["moe_held_chunks"] = float(metrics["moe_held_chunks"])
                    self.obs.registry.gauge("moe.held_rows").set(
                        row["moe_held_rows"])
                    self.obs.registry.gauge("moe.held_chunks").set(
                        row["moe_held_chunks"])
                metrics_hist.append(row)
                series.append(row)
                if on_step:
                    on_step(step, row)
                if self.telemetry is not None:
                    self.telemetry.observe(step, row)
                    stop = stop or self.telemetry.stop_requested
            pending.clear()

        for i in range(start, steps):
            self.timer.start()
            # the crash drill's kill point: fires BEFORE the step dispatch,
            # so the step that dies was never applied — exactly the state a
            # lost peer leaves behind
            inject.maybe(self._inj, "trainer.step")
            flush_now = (i + 1) % log_every == 0 or i + 1 == steps
            with self.obs.span("train.step", step=i + 1):
                batch = self._make_batch()
                with self.obs.span("train.dispatch"):
                    state, metrics = self.step_fn(state, batch)
                if flush_now:
                    # sync inside the timed span so a flush step's dt (and
                    # span) covers the compute it absorbs; non-flush steps
                    # record dispatch-side timing only
                    with self.obs.span("train.sync"):
                        jax.block_until_ready(metrics)
            dt = self.timer.stop()
            step_hist.observe(dt)
            pending.append((i + 1, metrics, dt))
            if self.hb:
                self._beat(i + 1, dt)
            if flush_now:
                with self.obs.span("train.flush"):
                    _flush()
            every = self.tcfg.checkpoint_every
            if self.ckpt is not None and ((i + 1) % every == 0
                                          or i + 1 == steps):
                self.save(i + 1, state)
            if stop:
                # telemetry early-stop: checkpoint what we have, end cleanly
                if (self.ckpt is not None and (i + 1) % every
                        and i + 1 != steps):
                    self.save(i + 1, state)
                break
        if self.ckpt is not None:
            self.ckpt.wait()
        return state, metrics_hist

    def _beat(self, step: int, dt: float):
        """Heartbeat with injectable failure modes: "dead" drops the beat
        entirely (the process looks gone to the FailureDetector after its
        timeout); "torn" writes an unparseable file in its place (a beat
        torn mid-write — read_all treats it as missing this round)."""
        ev = self._inj.poke("heartbeat") if self._inj is not None else None
        if ev is not None and ev.kind == "dead":
            return
        if ev is not None and ev.kind == "torn":
            import os
            with open(os.path.join(self.hb.dir,
                                   f"hb_{self.process}.json"), "w") as f:
                f.write('{"process": ')  # torn mid-write
            return
        self.hb.beat(self.process, step, dt)

    def save(self, step: int, state):
        if self.zero1:
            payload = {"step": state.step, "params": state.params,
                       "mu": state.mu, "nu": state.nu, "master": state.master}
        else:
            payload = {"step": state.step, "params": state.params,
                       "opt": dict(state.opt._asdict())}
            if state.router_bias is not None:
                payload["router_bias"] = state.router_bias
        self.ckpt.save(step, payload, process=self.process,
                       extra={"data_state": self.loader.snapshot()})
