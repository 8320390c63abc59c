"""Fault-tolerant distributed training loop.

Composes every substrate: config -> model -> sharded params/opt-state ->
jit'd train step (donated buffers) -> synthetic data stream -> checkpoint
manager (async, atomic, retained) -> straggler monitor -> elastic re-mesh
on injected/observed failures.

Two execution modes:
  * "pjit"          — GSPMD sharding from ShardingPolicy (the production
                      path; TP+FSDP per config);
  * "dp_compressed" — shard_map pure data parallelism with int8+error-
                      feedback gradient all-reduce (optim/compression.py):
                      the cross-pod bandwidth saver, demonstrated end-to-end.

Failure handling contract: a step raising FailureInjected (tests) or any
XlaRuntimeError (real device loss) triggers restore-from-checkpoint; if the
failure reports lost hosts, the mesh is shrunk (runtime/elastic.py) before
re-jitting.  Determinism: the data stream is a pure function of step, so
resume replays identical batches.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.data.synthetic import TokenStreamSpec, batch_at
from repro.models import steps as model_steps
from repro.models.config import ModelConfig
from repro.models.registry import build_model
from repro.models.sharding import make_policy
from repro.optim import adamw
from repro.optim.compression import compress_tree_psum
from repro.runtime import elastic, straggler


class FailureInjected(RuntimeError):
    def __init__(self, msg: str, lost_hosts: int = 0):
        super().__init__(msg)
        self.lost_hosts = lost_hosts


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 20
    ckpt_every: int = 5
    ckpt_dir: str = "/tmp/repro_ckpt"
    log_every: int = 5
    mode: str = "pjit"              # pjit | dp_compressed
    seed: int = 0
    straggler: straggler.StragglerConfig = dataclasses.field(
        default_factory=straggler.StragglerConfig)


class TrainLoop:
    def __init__(self, cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                 loop_cfg: LoopConfig, mesh: Mesh,
                 data_spec: Optional[TokenStreamSpec] = None,
                 failure_hook: Optional[Callable[[int], None]] = None):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.loop = loop_cfg
        self.mesh = mesh
        self.data_spec = data_spec or TokenStreamSpec(
            vocab=cfg.vocab, seq_len=128, global_batch=8, seed=loop_cfg.seed)
        self.failure_hook = failure_hook
        self.manager = CheckpointManager(loop_cfg.ckpt_dir)
        self.timer = straggler.StepTimer()
        self.strag_state = straggler.StragglerState()
        self.metrics_log: list = []
        self._build()

    # -- construction --------------------------------------------------------

    def _build(self) -> None:
        cfg, mesh = self.cfg, self.mesh
        self.model = build_model(cfg)
        self.policy = make_policy(cfg, mesh)
        shapes = self.model.init_shapes()
        self.param_shardings = self.policy.params_shardings(cfg, shapes)
        key = jax.random.PRNGKey(self.loop.seed)

        if self.loop.mode == "dp_compressed":
            self._build_dp_compressed(key)
            return

        init = jax.jit(self.model.init, out_shardings=self.param_shardings)
        self.params = init(key)
        opt_shapes = jax.eval_shape(
            partial(adamw.init, self.opt_cfg), shapes)
        self.opt_shardings = jax.tree.map(
            lambda s: s, {"m": self.param_shardings,
                          "v": self.param_shardings,
                          "step": NamedSharding(mesh, P())})
        self.opt_state = jax.jit(
            partial(adamw.init, self.opt_cfg),
            out_shardings=self.opt_shardings)(self.params)
        step_fn = model_steps.make_train_step(cfg, self.opt_cfg,
                                              policy=self.policy)
        batch_sharding = NamedSharding(mesh, P(self.policy.dp_axes, None))
        self._batch_sharding = batch_sharding
        self.step_fn = jax.jit(
            step_fn,
            donate_argnums=(0, 1),
            out_shardings=(self.param_shardings, self.opt_shardings, None),
        )

    def _build_dp_compressed(self, key) -> None:
        """Pure-DP shard_map path with int8 error-feedback gradient psum."""
        cfg, mesh = self.cfg, self.mesh
        axis = self.policy.dp_axes[0]
        self.params = self.model.init(key)
        self.opt_state = adamw.init(self.opt_cfg, self.params)
        self.err_state = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), self.params)

        def local_step(params, opt_state, err, tokens, labels):
            def loss_fn(p):
                l, m = model_steps.loss_fn(cfg, p,
                                           {"tokens": tokens,
                                            "labels": labels})
                return l, m
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads, err = compress_tree_psum(grads, axis, err)
            params, opt_state, om = adamw.update(self.opt_cfg, grads,
                                                 opt_state, params)
            metrics = dict(metrics, **om,
                           loss=jax.lax.pmean(metrics["loss"], axis))
            return params, opt_state, err, metrics

        rep = P()
        dp = P(axis)
        self.step_fn = jax.jit(shard_map(
            local_step, mesh=mesh,
            in_specs=(rep, rep, rep, dp, dp),
            out_specs=(rep, rep, rep, rep),
            check_vma=False))

    # -- data -----------------------------------------------------------------

    def _batch(self, step: int) -> Dict[str, jax.Array]:
        host = batch_at(self.data_spec, step)
        if self.loop.mode == "dp_compressed":
            return host
        return {k: jax.device_put(v, self._batch_sharding)
                for k, v in host.items()}

    # -- checkpoint -------------------------------------------------------------

    def _save(self, step: int) -> None:
        tree = {"params": self.params, "opt": self.opt_state}
        self.manager.save(step, tree,
                          metadata={"step": step,
                                    "data_seed": self.data_spec.seed})

    def _restore(self) -> int:
        like = {"params": jax.tree.map(np.asarray, self.params),
                "opt": jax.tree.map(np.asarray, self.opt_state)}
        shardings = None
        if self.loop.mode == "pjit":
            shardings = {"params": self.param_shardings,
                         "opt": self.opt_shardings}
        self.manager.wait()
        out = self.manager.restore_latest(like, shardings)
        if out is None:
            return 0
        tree, meta, step = out
        self.params, self.opt_state = tree["params"], tree["opt"]
        return step + 1

    # -- main loop ---------------------------------------------------------------

    def run(self) -> Dict[str, float]:
        step = self._restore()
        while step < self.loop.total_steps:
            try:
                if self.failure_hook is not None:
                    self.failure_hook(step)
                t0 = time.perf_counter()
                batch = self._batch(step)
                if self.loop.mode == "dp_compressed":
                    (self.params, self.opt_state, self.err_state,
                     metrics) = self.step_fn(self.params, self.opt_state,
                                             self.err_state,
                                             batch["tokens"],
                                             batch["labels"])
                else:
                    self.params, self.opt_state, metrics = self.step_fn(
                        self.params, self.opt_state, **batch)
                jax.block_until_ready(metrics["loss"])
                self.timer.record(time.perf_counter() - t0)
                self._monitor(step, metrics)
                if step % self.loop.ckpt_every == 0:
                    self._save(step)
                step += 1
            except FailureInjected as e:
                self._recover(e)
                step = self._restore()
        self.manager.wait()
        self.manager.close()
        return self.timer.summary()

    def _monitor(self, step: int, metrics) -> None:
        loss = float(metrics["loss"])
        self.metrics_log.append({"step": step, "loss": loss,
                                 "time_s": self.timer.last()})
        # single-host container: feed local time as a 1-host report
        self.strag_state, flagged = straggler.update(
            self.loop.straggler, self.strag_state, [self.timer.last()])
        if flagged:
            self.metrics_log[-1]["stragglers"] = flagged

    def _recover(self, e: FailureInjected) -> None:
        """Failure path: optionally shrink the mesh, rebuild jit artifacts."""
        if e.lost_hosts > 0 and self.loop.mode == "pjit":
            plan = elastic.shrink_data_axis(self.mesh, e.lost_hosts)
            self.mesh = elastic.build_mesh(plan)
        # re-jit against the (possibly new) mesh; params come from restore
        self._build()


__all__ = ["TrainLoop", "LoopConfig", "FailureInjected"]
