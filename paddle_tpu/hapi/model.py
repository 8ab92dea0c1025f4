"""High-level Model API (reference: python/paddle/hapi/model.py — Model:907,
fit:1045, evaluate, predict, save/load; Keras-style train loop).

TPU-native: `prepare()` builds ONE jitted train step (forward+backward+update)
— the whole-program compilation that replaces the reference's dual
dygraph/static execution paths.
"""
from __future__ import annotations

import math
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import framework
from .. import observability as obs
from ..framework import debug
from ..framework import random as fw_random
from ..framework.errors import enforce
from ..framework.log import vlog
from ..io import DataLoader
from ..metric import Metric

__all__ = ["Model"]


def _tuplify(x):
    return x if isinstance(x, (tuple, list)) else (x,)


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._loss = None
        self._optimizer = None
        self._metrics: List[Metric] = []
        self._train_step = None
        self._eval_fn = None
        self._opt_state = None
        self._amp_level = None
        self._nonfinite_budget: Optional[int] = None
        self._nonfinite_skipped = 0
        self._supervisor = None  # set by RunSupervisor.attach / fit()
        # -- telemetry (ISSUE 3): last train_batch's dispatch/readback
        # split + cached MFU accounting inputs
        self._last_batch_timing: Optional[dict] = None
        self._obs_n_params: Optional[int] = None
        self._obs_flops_token: Optional[float] = None
        self._obs_seq_len: Optional[int] = None
        self._obs_peak: Optional[float] = None
        self._obs_step = 0

    # -- setup ------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, nonfinite_skip_budget: Optional[int] = None):
        """``nonfinite_skip_budget``: when set, a train batch whose loss
        comes back nan/inf is SKIPPED (no parameter/optimizer update)
        instead of poisoning the run — up to that many times, counted in
        ``nonfinite_skipped`` (surfaced in fit() batch logs); one more
        raises ``FloatingPointError``.  ``None`` (default) keeps the
        historical behavior: the update applies whatever the loss."""
        self._optimizer = optimizer
        # persistent compile cache: the train step built below is the
        # most expensive program the framework compiles — a warm process
        # loads it from disk
        obs.enable_persistent_cache()
        # ISSUE 8: a ZeRO-1 ShardedOptimizer (or a fleet wrapper over
        # one) resolves its mesh/axis/shard-count binding NOW, so the
        # fleet mesh active at prepare time is the one the jitted step's
        # sharding constraints are laid out against
        if hasattr(optimizer, "bind_mesh"):
            optimizer.bind_mesh()
        self._loss = loss
        self._metrics = _tuplify(metrics) if metrics is not None else []
        self._nonfinite_budget = (None if nonfinite_skip_budget is None
                                  else int(nonfinite_skip_budget))
        self._nonfinite_skipped = 0
        self._amp_level = (amp_configs or {}).get("level") if isinstance(
            amp_configs, dict) else amp_configs

        net, opt, loss_fn = self.network, self._optimizer, self._loss
        amp_level = self._amp_level

        def train_step(trainable, rest, opt_state, key, lr_override, *data):
            """Differentiate w.r.t. trainable params only; buffers (`rest`)
            flow through mutable apply.  ``lr_override``: traced scalar (or
            None) — set when the optimizer's lr is a stateful LRScheduler,
            whose .step() the LRScheduler callback drives (paddle
            convention)."""
            *inputs, label = data

            def compute_loss(tp):
                variables = {**rest, **tp}
                with fw_random.key_scope(key):
                    if amp_level:
                        from .. import amp as amp_mod
                        with amp_mod.auto_cast(level=amp_level):
                            out, newv = net.apply(variables, *inputs,
                                                  mutable=True)
                    else:
                        out, newv = net.apply(variables, *inputs, mutable=True)
                return loss_fn(out, label), (out, newv)

            (loss_v, (out, new_vars)), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(trainable)
            new_trainable, new_opt_state = opt.apply_gradients(
                grads, trainable, opt_state, lr=lr_override)
            merged = dict(new_vars)
            merged.update(new_trainable)
            # always traced (a few fused scalar reductions, ≙ the
            # operator.cc:1252 per-op scans) so FLAGS_check_nan_inf stays
            # runtime-togglable — the host only LOOKS at these when the
            # flag is set at call time (train_batch)
            finite = debug.finite_flags({"loss": loss_v, "grads": grads})
            # grad global norm: one fused reduction, fed to the run
            # supervisor's divergence guard (f32 accumulate so a bf16
            # overflow can't hide inside the statistic itself)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)) + 0.0)
            return loss_v, out, merged, new_opt_state, finite, gnorm

        def eval_fn(params, *data):
            *inputs, label = data
            out = net.apply(params, *inputs)
            return loss_fn(out, label) if loss_fn is not None else 0.0, out

        # compile/retrace accounting (ISSUE 4): every trace of the step
        # lands on the telemetry timeline as a `compile` record, and a
        # shape-churning argument is named by the retrace-storm detector
        self._train_step = obs.track_jit(
            jax.jit(train_step), name="hapi.train_step",
            arg_names=("trainable", "rest", "opt_state", "key",
                       "lr_override", "data[0]", "data[1]", "data[2]",
                       "data[3]", "data[4]", "data[5]"))
        self._eval_fn = obs.track_jit(jax.jit(eval_fn),
                                      name="hapi.eval_fn")

    # -- per-batch --------------------------------------------------------
    def _variables(self):
        return self.network.state_dict()

    def train_batch(self, inputs, labels=None):
        enforce(self._train_step is not None, "call prepare() first")
        self.network.train()
        variables = self._variables()
        trainable = self.network.trainable_variables()
        rest = {k: v for k, v in variables.items() if k not in trainable}
        if self._opt_state is None:
            self._opt_state = self._optimizer.init(trainable)
        data = [jnp.asarray(np.asarray(x)) for x in
                (*_tuplify(inputs), *_tuplify(labels))]
        key = fw_random.next_key()
        from ..optimizer import lr as lr_mod
        sup = self._supervisor
        lr_override = None
        if isinstance(getattr(self._optimizer, "_lr", None),
                      lr_mod.LRScheduler):
            # stateful scheduler: the current value applies until someone
            # (the LRScheduler callback, or the user) calls .step()
            lr_override = jnp.asarray(self._optimizer._lr.get_lr(),
                                      jnp.float32)
        if sup is not None and sup.guard.lr_scale != 1.0:
            # divergence guard's LOWER_LR escalation: sticky backoff on
            # top of whatever schedule is active
            lr_override = jnp.asarray(
                self._optimizer.get_lr() * sup.guard.lr_scale, jnp.float32)
        if (sup is not None and sup.integrity is not None
                and sup.integrity.enabled):
            # replay-audit stash (ISSUE 11): references to this step's
            # pre-state and exact inputs (jax arrays are immutable, so
            # this is pointer assignment, not a copy)
            if sup.integrity.replay_fn is None:
                sup.integrity.replay_fn = self._integrity_replay
            sup.integrity.stash_replay(sup.gstep + 1,
                                       self._supervised_state(),
                                       (data, key, lr_override))
        try:
            if sup is not None:
                # the armed region covers the jitted step AND the host
                # sync on its results — where a hung collective actually
                # blocks
                with sup.watchdog.armed("train_batch"):
                    with obs.span("dispatch") as sp_d:
                        loss, out, new_params, new_opt_state, finite, \
                            gnorm = self._train_step(
                                trainable, rest, self._opt_state,
                                key, lr_override, *data)
                    # dispatch is asynchronous: the readback waits for
                    # the device, so this span absorbs the device compute
                    with obs.span("readback") as sp_r:
                        loss_v = sup.filter_loss(float(loss))
                        gnorm_v = float(gnorm)
                self._last_batch_timing = {"dispatch_s": sp_d.elapsed,
                                           "readback_s": sp_r.elapsed}
                action = sup.guard_step(loss_v, gnorm_v,
                                        amp_active=bool(self._amp_level))
                from ..supervisor.guard import GuardAction
                if action != GuardAction.OK:
                    # SKIP / LOWER_LR / ROLLBACK all drop this batch's
                    # update (params AND optimizer state); ROLLBACK is
                    # latched on the supervisor for the driving loop to
                    # execute
                    return loss_v, [m.accumulate() for m in self._metrics]
            else:
                with obs.span("dispatch") as sp_d:
                    loss, out, new_params, new_opt_state, finite, _gnorm = \
                        self._train_step(trainable, rest, self._opt_state,
                                         key, lr_override, *data)
                with obs.span("readback") as sp_r:
                    loss_v = float(loss)
                self._last_batch_timing = {"dispatch_s": sp_d.elapsed,
                                           "readback_s": sp_r.elapsed}
        except Exception as e:
            # an allocator OOM kills the step AND the evidence — dump the
            # last-known per-device watermark table first (ISSUE 4)
            if obs.is_oom_error(e):
                obs.oom_postmortem(error=e, step=(
                    sup.gstep if sup is not None else self._obs_step))
            raise
        if debug.check_nan_inf_enabled():
            debug.assert_all_finite(finite, context="train_batch")
        if self._nonfinite_budget is not None and not math.isfinite(loss_v):
            # skip-step: drop this batch's update entirely (params AND
            # optimizer state) so one bad batch degrades gracefully;
            # exhausting the budget fails loudly — a persistent nan is a
            # bug, not noise
            self._nonfinite_skipped += 1
            if self._nonfinite_skipped > self._nonfinite_budget:
                raise FloatingPointError(
                    f"non-finite loss ({loss_v}) exceeded the skip budget "
                    f"of {self._nonfinite_budget}")
            vlog(0, "hapi: non-finite loss (%s) — skipping update (%d/%d)",
                 loss_v, self._nonfinite_skipped, self._nonfinite_budget)
            return loss_v, [m.accumulate() for m in self._metrics]
        self._opt_state = new_opt_state
        self.network.set_state_dict(new_params, strict=False)
        metrics = []
        for m in self._metrics:
            r = m.compute(np.asarray(out), np.asarray(data[-1]))
            m.update(*(r if isinstance(r, tuple) else (r,)))
            metrics.append(m.accumulate())
        return loss_v, metrics

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        params = self._variables()
        data = [jnp.asarray(np.asarray(x)) for x in
                (*_tuplify(inputs), *_tuplify(labels))]
        loss, out = self._eval_fn(params, *data)
        return float(loss), out

    def predict_batch(self, inputs):
        self.network.eval()
        params = self._variables()
        return self.network.apply(
            params, *[jnp.asarray(np.asarray(x)) for x in _tuplify(inputs)])

    # -- loops ------------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size: int = 1,
            epochs: int = 1, eval_freq: int = 1, log_freq: int = 10,
            save_dir: Optional[str] = None, shuffle: bool = True,
            num_workers: int = 0, verbose: int = 1, drop_last: bool = False,
            callbacks=None, supervisor=None):
        """``supervisor``: a :class:`paddle_tpu.supervisor.RunSupervisor`
        wrapping this run in the full health loop — watchdog around every
        batch, heartbeats, divergence guard (skip → lower-LR → rollback),
        and budget-bounded auto-rollback to the last committed
        checkpoint.  See docs/ARCHITECTURE.md "Run supervision"."""
        from ..optimizer import lr as lr_mod
        from .callbacks import (CallbackList, LRScheduler as LRSchedulerCB,
                                ModelCheckpoint, ProgBarLogger)
        if not isinstance(train_data, DataLoader):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers)
        else:
            train_loader = train_data
        cbs = CallbackList(list(callbacks or []))
        if not any(isinstance(c, ProgBarLogger) for c in cbs.callbacks):
            cbs.append(ProgBarLogger(log_freq=log_freq, verbose=verbose))
        if save_dir and not any(isinstance(c, ModelCheckpoint)
                                for c in cbs.callbacks):
            cbs.append(ModelCheckpoint(save_dir=save_dir))
        if (isinstance(getattr(self._optimizer, "_lr", None),
                       lr_mod.LRScheduler)
                and not any(isinstance(c, LRSchedulerCB)
                            for c in cbs.callbacks)):
            # paddle convention: fit drives per-step scheduling by default
            cbs.append(LRSchedulerCB(by_step=True))
        cbs.set_model(self)
        cbs.set_params({"epochs": epochs, "batch_size": batch_size,
                        "verbose": verbose, "save_dir": save_dir})
        self.stop_training = False
        history = {"loss": []}
        sup = supervisor
        if sup is not None:
            from ..supervisor.guard import GuardAction
            from ..supervisor.watchdog import StepTimeout
            sup.attach(self)
            if self._optimizer is not None and self._opt_state is None:
                # warm the optimizer state so every supervised checkpoint
                # (including the rollback templates) has one stable pytree
                self._opt_state = self._optimizer.init(
                    self.network.trainable_variables())
            sup.begin_run(initial_state=self._supervised_state())
        cbs.on_train_begin()
        try:
            for epoch in range(epochs):
                for m in self._metrics:
                    m.reset()
                cbs.on_epoch_begin(epoch)
                epoch_losses = []
                for step, (batch, data_s) in enumerate(
                        self._timed_batches(train_loader)):
                    cbs.on_train_batch_begin(step)
                    *inputs, label = batch
                    if sup is not None:
                        try:
                            with obs.span("step") as sp_step:
                                loss, metrics = self.train_batch(inputs,
                                                                 label)
                        except StepTimeout:
                            # watchdog fired: the step is dead, not the
                            # run — skip it, roll back when they repeat
                            if (sup.note_step_failure("step-timeout")
                                    == GuardAction.ROLLBACK):
                                self._supervised_rollback(sup)
                            cbs.on_train_batch_end(
                                step, {"loss": float("nan"),
                                       "supervisor": "step-timeout"})
                            if self.stop_training:
                                break
                            continue
                        good = sup.last_action in (None, GuardAction.OK)
                        if sup.pending_rollback:
                            self._supervised_rollback(sup)
                        elif sup.pending_resize is not None:
                            # elastic resize (ISSUE 9): lost worker or a
                            # scale signal — re-form the mesh at the new
                            # width and resume from last_good_step
                            self._supervised_resize(sup)
                        elif sup.pending_integrity is not None:
                            # state-integrity heal (ISSUE 11): a desync
                            # verdict — majority members publish the
                            # resync offer, suspects climb the
                            # resync → rollback → evict ladder
                            self._supervised_integrity_heal(sup)
                        else:
                            # checkpoint only states a good update built
                            sup.note_step_ok(
                                self._supervised_state() if good else None)
                    else:
                        good = True
                        with obs.span("step") as sp_step:
                            loss, metrics = self.train_batch(inputs, label)
                    self._record_step_telemetry(data_s, sp_step.elapsed,
                                                label, loss)
                    history["loss"].append(loss)
                    if good:
                        epoch_losses.append(loss)
                    logs = {"loss": loss}
                    if sup is not None and not good:
                        logs["supervisor"] = sup.last_action
                    if self._nonfinite_budget is not None:
                        logs["nonfinite_skipped"] = self._nonfinite_skipped
                    for m, v in zip(self._metrics, metrics):
                        logs[m.name()] = v[0] if isinstance(v, list) else v
                    cbs.on_train_batch_end(step, logs)
                    if self.stop_training:
                        break
                # with a skip guard on, skipped batches' nan losses are
                # excluded from the epoch mean (they applied no update)
                _mean = (np.nanmean if self._nonfinite_budget is not None
                         else np.mean)
                epoch_logs = {"loss": float(_mean(epoch_losses))
                              if epoch_losses else float("nan")}
                if eval_data is not None and (epoch + 1) % eval_freq == 0:
                    cbs.on_eval_begin()
                    eval_res = self.evaluate(eval_data,
                                             batch_size=batch_size,
                                             verbose=verbose)
                    cbs.on_eval_end(eval_res)
                    # eval metrics reach on_epoch_end (EarlyStopping
                    # monitors)
                    epoch_logs.update({f"eval_{k}" if k == "loss" else k: v
                                       for k, v in eval_res.items()})
                cbs.on_epoch_end(epoch, epoch_logs)
                if self.stop_training:
                    break
        except BaseException:
            if sup is not None:
                sup.end_run("failed")
                self._supervisor = None
            raise
        if sup is not None:
            sup.end_run("completed")
            self._supervisor = None
        cbs.on_train_end()
        return history

    # -- telemetry plumbing (ISSUE 3) -------------------------------------
    @staticmethod
    def _timed_batches(loader):
        """Iterate ``loader`` yielding ``(batch, data_wait_seconds)`` —
        the data-wait half of the per-step breakdown."""
        it = iter(loader)
        while True:
            t0 = time.perf_counter()
            try:
                with obs.span("data_load"):
                    batch = next(it)
            except StopIteration:
                return
            yield batch, time.perf_counter() - t0

    def _record_step_telemetry(self, data_s: float, step_s: float, label,
                               loss) -> None:
        """One ``step`` record per train batch: wall time split into
        data-wait / dispatch (compute) / host-readback, tokens/sec, and
        live MFU against the chip's peak (``observability.mfu``) —
        emitted to whatever sinks are attached, accumulated in the
        registry's histograms either way."""
        try:
            reg = obs.get_registry()
            timing = self._last_batch_timing or {}
            lab = np.asarray(label)
            tokens = max(1, int(lab.size))
            seq_len = int(lab.shape[-1]) if lab.ndim >= 2 else None
            if self._obs_n_params is None:
                self._obs_n_params = obs.param_count(
                    self.network.state_dict())
                self._obs_peak = obs.peak_flops_per_sec()
            if self._obs_flops_token is None or seq_len != self._obs_seq_len:
                cfg = getattr(self.network, "config", None)
                self._obs_flops_token = obs.flops_per_token(
                    self._obs_n_params,
                    num_layers=getattr(cfg, "num_layers", None),
                    hidden_size=getattr(cfg, "hidden_size", None),
                    seq_len=seq_len)
                self._obs_seq_len = seq_len
            total_s = max(1e-9, data_s + step_s)
            tps = tokens / total_s
            mfu_v = obs.mfu(tps, self._obs_flops_token, self._obs_peak)
            compute_ms = timing.get("dispatch_s", 0.0) * 1e3
            readback_ms = timing.get("readback_s", 0.0) * 1e3
            reg.histogram("step.time_ms").observe(total_s * 1e3)
            reg.histogram("step.data_ms").observe(data_s * 1e3)
            reg.histogram("step.compute_ms").observe(compute_ms)
            reg.histogram("step.readback_ms").observe(readback_ms)
            reg.counter("step.count").inc()
            reg.counter("step.tokens").inc(tokens)
            reg.gauge("step.tokens_per_sec").set(tps)
            if mfu_v is not None:    # None: device has no known peak
                reg.gauge("step.mfu").set(mfu_v)
            sup = self._supervisor
            cur_step = sup.gstep if sup is not None else self._obs_step
            # where-is-it-now gauges for the live monitor's /statusz
            # page (ISSUE 5)
            reg.gauge("step.current").set(cur_step)
            reg.gauge("step.loss").set(float(loss))
            # HBM watermark sample on its PTPU_MEM_SAMPLE_EVERY cadence
            # (no-op off cadence / on backends without allocator stats)
            obs.get_sampler().sample(cur_step)
            reg.emit("step",
                     step=cur_step,
                     step_time_ms=total_s * 1e3, data_ms=data_s * 1e3,
                     compute_ms=compute_ms, readback_ms=readback_ms,
                     tokens=tokens, tokens_per_sec=tps, mfu=mfu_v,
                     loss=float(loss))
            self._obs_step += 1
        except Exception as e:
            # telemetry must never take the training loop down with it
            vlog(1, "hapi: step telemetry failed: %r", e)

    # -- supervision plumbing (ISSUE 2) -----------------------------------
    def _supervised_state(self):
        """The pytree the run supervisor checkpoints and rolls back —
        parameters + buffers, plus optimizer state once it exists."""
        state = {"params": dict(self.network.state_dict())}
        if self._opt_state is not None:
            state["opt"] = self._opt_state
        return state

    def _load_supervised_state(self, state) -> None:
        self.network.set_state_dict(state["params"], strict=False)
        if "opt" in state:
            self._opt_state = state["opt"]

    def _supervised_rollback(self, sup, reason: Optional[str] = None
                             ) -> None:
        """Restore the last committed good step into the live model (the
        pristine t0 state when nothing has been committed yet)."""
        state, _start = sup.perform_rollback(
            lambda: (sup.initial_state if sup.initial_state is not None
                     else self._supervised_state()),
            lambda: self._supervised_state(), reason)
        self._load_supervised_state(state)

    def _supervised_integrity_heal(self, sup) -> None:
        """Execute a latched state-integrity heal (ISSUE 11); the live
        model adopts whatever state the healing ladder lands on — the
        majority state (resync), a digest-verified checkpoint
        (rollback), or the re-formed fleet's state (evict)."""
        state, _start = sup.perform_integrity_heal(
            lambda: (sup.initial_state if sup.initial_state is not None
                     else self._supervised_state()),
            lambda: self._supervised_state(),
            self._supervised_state())
        self._load_supervised_state(state)

    def _integrity_replay(self, state, stashed):
        """Deterministic re-run of one stashed microbatch for the replay
        audit: same inputs, same RNG key, same LR — the jitted step is
        pure, so two replays that disagree indict software
        nondeterminism and a replay that disagrees with the live state
        indicts the hardware (state damaged outside the computed path)."""
        data, key, lr_override = stashed
        params = state["params"]
        tv = self.network.trainable_variables()
        # same container type + order as the live step — the optimizer
        # state's treedef is structural, not just keyed
        trainable = type(tv)((k, params[k]) for k in tv)
        rest = {k: v for k, v in params.items() if k not in tv}
        _loss, _out, merged, new_opt_state, _finite, _g = self._train_step(
            trainable, rest, state["opt"], key, lr_override, *data)
        return {"params": dict(merged), "opt": new_opt_state}

    def _supervised_resize(self, sup) -> None:
        """Execute a latched elastic resize (ISSUE 9): the coordinator
        re-forms the mesh at the new width and re-shards the last
        committed state onto it; the live model adopts the restored
        (rewound) state and training continues — the jitted step simply
        retraces against the new placements."""
        state, _start = sup.perform_resize(
            lambda: (sup.initial_state if sup.initial_state is not None
                     else self._supervised_state()),
            lambda: self._supervised_state())
        self._load_supervised_state(state)

    def evaluate(self, eval_data, batch_size: int = 1, log_freq: int = 10,
                 verbose: int = 1, num_workers: int = 0):
        if not isinstance(eval_data, DataLoader):
            loader = DataLoader(eval_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = eval_data
        for m in self._metrics:
            m.reset()
        losses = []
        for batch in loader:
            *inputs, label = batch
            loss, out = self.eval_batch(inputs, label)
            losses.append(loss)
            for m in self._metrics:
                r = m.compute(np.asarray(out), np.asarray(label))
                m.update(*(r if isinstance(r, tuple) else (r,)))
        result = {"loss": float(np.mean(losses)) if losses else 0.0}
        for m in self._metrics:
            result[m.name()] = m.accumulate()
        if verbose:
            print("Eval:", result)  # noqa: print
        return result

    def predict(self, test_data, batch_size: int = 1, num_workers: int = 0):
        if not isinstance(test_data, DataLoader):
            loader = DataLoader(test_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = test_data
        outs = []
        for batch in loader:
            inputs = batch[:-1] if isinstance(batch, (tuple, list)) and \
                len(batch) > 1 else _tuplify(batch)
            outs.append(np.asarray(self.predict_batch(list(inputs))))
        return outs

    # -- io ---------------------------------------------------------------
    def save(self, path: str):
        framework.save(self.network.state_dict(), path + ".pdparams")
        if self._opt_state is not None:
            framework.save(self._opt_state, path + ".pdopt")

    def load(self, path: str, reset_optimizer: bool = False):
        self.network.set_state_dict(framework.load(path + ".pdparams"))
        if not reset_optimizer:
            import os
            if os.path.exists(path + ".pdopt"):
                self._opt_state = framework.load(path + ".pdopt")

    def parameters(self):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        lines, total = [], 0
        for name, p in self.network.named_parameters():
            n = int(np.prod(p.shape))
            total += n
            lines.append(f"  {name:40s} {str(p.shape):20s} {n}")
        out = "\n".join(lines) + f"\nTotal params: {total}"
        print(out)  # noqa: print
        return {"total_params": total}
