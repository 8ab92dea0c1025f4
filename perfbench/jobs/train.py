"""The training job: steps of one donated jit program, chained, with a fresh
seeded batch made on the host and transferred inside the loop.

Set-up: model, optimizer and state from ``--seed``; ahead-of-time compile (or
cache load); the plain reference's loss on the first batch from the initial
parameters; the first step (checked against it) and the warm-up steps.
Window: from the first measured step's dispatch to ``block_until_ready`` of
the last one's loss; the host stays at most ``in_flight`` steps ahead.
A traced run goes on for ``trace_s`` seconds after the window with the
profiler on, so that starting and stopping it disturb nothing measured.
"""
from __future__ import annotations

import math
import time

import numpy as np


def run(ctx) -> dict:
    import jax

    t = ctx.traffic
    system = ctx.system
    parts = ctx.setup_parts
    spans = ctx.spans
    clock = time.perf_counter

    t0 = clock()
    params, opt_state, step_jit = system.build_for_training()
    jax.block_until_ready(params)
    parts["weights_s"] = clock() - t0

    gen = ctx.manifest.load_module("kinds", t["kind"]).make(
        t, ctx.seed, system.shape["vocab"])
    key = jax.random.key(ctx.seed % (2 ** 31 - 1))
    first_ids = gen.next_batch()

    t0 = clock()
    step = step_jit.lower(params, opt_state, system.put_batch(first_ids),
                          key).compile()
    parts["compile_or_load_s"] = clock() - t0
    plan = step.memory_analysis()
    planned = None
    if plan is not None:
        planned = int(plan.argument_size_in_bytes + plan.output_size_in_bytes
                      - plan.alias_size_in_bytes + plan.temp_size_in_bytes)

    # the reference, on the first batch, from the parameters as initialised
    # (the step donates them, so the reference goes first)
    t0 = clock()
    ref_fn = system.reference_loss_fn()
    ref_params = system.reference_params(params)
    chunk = int(t.get("reference_chunk", 2))
    ref_losses = [float(ref_fn(ref_params, system.put_batch(
        first_ids[i:i + chunk]))) for i in range(0, len(first_ids), chunk)]
    ref_loss = float(np.mean(ref_losses))
    del ref_params
    parts["reference_s"] = clock() - t0

    t0 = clock()
    loss, params, opt_state = step(params, opt_state,
                                   system.put_batch(first_ids), key)
    first_loss = float(loss)
    for _ in range(int(t["warmup_steps"])):
        loss, params, opt_state = step(
            params, opt_state, system.put_batch(gen.next_batch()), key)
    jax.block_until_ready(loss)
    parts["warmup_s"] = clock() - t0

    in_flight = int(t.get("in_flight", 2))
    losses = []

    def one_step():
        nonlocal params, opt_state
        with spans.span("batch_make"):
            ids = gen.next_batch()
        with spans.span("batch_put"):
            dev_ids = system.put_batch(ids)
        with spans.span("train_step.dispatch"):
            loss, params, opt_state = step(params, opt_state, dev_ids, key)
        losses.append(loss)
        if len(losses) > in_flight:
            with spans.span("wait"):
                jax.block_until_ready(losses[-1 - in_flight])

    ctx.open_window()
    w0 = clock()
    while clock() - w0 < ctx.seconds:
        one_step()
    with spans.span("wait"):
        jax.block_until_ready(losses[-1])
    w1 = clock()
    ctx.close_window()
    n_window = len(losses)

    if ctx.trace:
        with ctx.tracing():
            s0 = clock()
            while clock() - s0 < ctx.trace_s:
                one_step()
            with spans.span("wait"):
                jax.block_until_ready(losses[-1])

    values = [float(x) for x in losses]
    window_losses = values[:n_window]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    tol = float(t["loss_tolerance"])
    tail = window_losses[-5:]
    checks = {
        "first_loss": first_loss, "reference_loss": ref_loss,
        "loss_abs_diff": abs(first_loss - ref_loss), "loss_tolerance": tol,
        "window_last_losses_mean": float(np.mean(tail)),
        "min_loss_drop": float(t.get("min_loss_drop", 0.0)),
        "falls": float(np.mean(tail))
        < first_loss - float(t.get("min_loss_drop", 0.0)),
    }
    correct = (failed == 0 and math.isfinite(first_loss)
               and checks["loss_abs_diff"] <= tol and checks["falls"])
    return {
        "job": "train", "correct": bool(correct), "checks": checks,
        "attempted": n_window, "failed": failed,
        "window": {"t0": w0, "t1": w1, "seconds": w1 - w0},
        "train": {"steps": n_window,
                  "tokens_per_step": gen.tokens_per_step,
                  "tokens": n_window * gen.tokens_per_step,
                  "batch": gen.batch, "seq": gen.seq,
                  "n_params": system.n_params()},
        "planned_bytes": planned,
        "samples": {"steps_in_window": n_window},
    }
