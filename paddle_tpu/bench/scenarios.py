"""Scenario registry (ISSUE 13) — the BASELINE.json workload matrix.

Each scenario is one registered function ``fn(mode) -> payload`` where
``mode`` is ``"smoke"`` (CPU-sized, CI) or ``"full"`` (the real
BASELINE shapes).  The payload carries only what the scenario itself
measured — ``runner.run_scenario`` brackets it with the compile window,
bytes-on-wire delta and fingerprint stamping, and assembles the one
schema row.

Matrix (ROADMAP 5b):

==================== =====================================================
gpt_pretrain_fused   GPT causal-LM train step, fused transformer block
gpt_pretrain_unfused same config, fused block off (the PR 7 A/B axes)
moe                  GPT with MoE FFN layers (``distributed/moe.py``)
long_context         Ulysses sequence-parallel GPT over the ``sp`` axis
resnet               ResNet train step (18 smoke / 50 ImageNet-config)
mnist                LeNet MNIST-shape train step
serve                continuous-batching decode through the PR 6 engine
serve_fleet          routed decode over 2 replicas incl. one failover
==================== =====================================================
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List

from . import harness

__all__ = ["register", "get", "names", "SCENARIOS"]

SCENARIOS: Dict[str, Callable[[str], Dict[str, Any]]] = {}


def register(name: str):
    def deco(fn):
        SCENARIOS[name] = fn
        fn.__scenario_name__ = name
        return fn
    return deco


def get(name: str) -> Callable[[str], Dict[str, Any]]:
    if name not in SCENARIOS:
        raise KeyError(f"unknown scenario {name!r}; have "
                       f"{', '.join(sorted(SCENARIOS))}")
    return SCENARIOS[name]


def names() -> List[str]:
    return list(SCENARIOS)


# -- shared GPT train-step scaffolding --------------------------------------
def _gpt_train_payload(cfg, B: int, S: int, steps: int, warmup: int,
                       shard_data: bool = False) -> Dict[str, Any]:
    """Build + measure one GPT causal-LM train step; the common core of
    the gpt/moe/long_context scenarios.  ``shard_data``: route batches
    through ``dist.shard_batch`` (sequence-parallel meshes)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.framework import random as fw_random
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.observability.compilation import track_jit
    from paddle_tpu.observability.mfu import (flops_per_token, mfu,
                                              param_count)

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    if shard_data:
        from paddle_tpu.distributed.parallel import (
            device_put_sharded_variables)
        device_put_sharded_variables(model)
    params = model.state_dict()
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01)
    opt_state = opt.init(params)
    n_params = param_count(params)

    def train_step(p, s, ids, labels, key):
        def loss_fn(q):
            with fw_random.key_scope(key):
                loss, _ = model.apply(q, ids, labels=labels)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_s = opt.apply_gradients(grads, p, s)
        return loss, new_p, new_s

    jitted = track_jit(jax.jit(train_step, donate_argnums=(0, 1)),
                       name="bench.gpt_step",
                       arg_names=("params", "opt_state", "inputs",
                                  "labels", "key"))
    rng = np.random.RandomState(0)

    def make_batch(i):
        ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
        labels = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
        if shard_data:
            import paddle_tpu.distributed as dist
            return (dist.shard_batch(ids), dist.shard_batch(labels))
        return (jnp.asarray(ids), jnp.asarray(labels))

    # static footprint BEFORE the loop: donated buffers are gone after
    ids0, labels0 = make_batch(0)
    peak = harness.peak_hbm(jitted, params, opt_state, ids0, labels0,
                            jax.random.key(0))

    state = {"p": params, "s": opt_state}

    def step_fn(i, batch):
        ids, labels = batch
        loss, state["p"], state["s"] = jitted(
            state["p"], state["s"], ids, labels,
            jax.random.fold_in(jax.random.key(0), i))
        return loss

    m = harness.measure_steps(step_fn, make_batch, steps, warmup)
    p50 = harness.pct(sorted(m["step_times_ms"]), 50) or 1.0
    tok_s = B * S / (p50 / 1e3)
    flops_tok = flops_per_token(n_params, num_layers=cfg.num_layers,
                                hidden_size=cfg.hidden_size, seq_len=S,
                                causal=True)
    return {
        "config": {"batch": B, "seq_len": S, "steps": steps,
                   "warmup": warmup, "params_m": n_params / 1e6,
                   "num_layers": cfg.num_layers,
                   "hidden_size": cfg.hidden_size},
        "step_times_ms": m["step_times_ms"],
        "phases_ms": m["phases_ms"],
        "collective_by_op": m.get("collective_by_op"),
        "tokens_per_sec": tok_s,
        "mfu": mfu(tok_s, flops_tok),
        "peak_hbm_bytes": peak,
        "extra": {"warmup_s": m["warmup_s"],
                  "final_loss": m["final_value"]},
    }


def _gpt_cfg(mode: str, **kw):
    from paddle_tpu.models import gpt_125m, gpt_tiny
    if mode == "full":
        return gpt_125m(dtype="bfloat16", hidden_dropout=0.0,
                        attention_dropout=0.0, use_pallas_attention=True,
                        max_position_embeddings=2048, **kw)
    return gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0, **kw)


def _gpt_shape(mode: str):
    return ((8, 2048, 10, 3) if mode == "full" else (2, 128, 4, 1))


@register("gpt_pretrain_fused")
def gpt_pretrain_fused(mode: str) -> Dict[str, Any]:
    B, S, steps, warmup = _gpt_shape(mode)
    return _gpt_train_payload(_gpt_cfg(mode, use_fused_block=True),
                              B, S, steps, warmup)


@register("gpt_pretrain_unfused")
def gpt_pretrain_unfused(mode: str) -> Dict[str, Any]:
    B, S, steps, warmup = _gpt_shape(mode)
    return _gpt_train_payload(_gpt_cfg(mode, use_fused_block=False),
                              B, S, steps, warmup)


@register("moe")
def moe(mode: str) -> Dict[str, Any]:
    """GPT with MoE FFN layers (every other layer; gshard top-2).  On
    one device the dispatch/combine runs unsharded — the capacity math
    and aux loss are identical, which is what the row tracks."""
    from paddle_tpu.models import gpt_125m, gpt_tiny
    if mode == "full":
        cfg = gpt_125m(dtype="bfloat16", hidden_dropout=0.0,
                       attention_dropout=0.0, use_pallas_attention=True,
                       max_position_embeddings=2048,
                       moe_num_experts=8, moe_every=2)
        B, S, steps, warmup = 8, 2048, 10, 3
    else:
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0,
                       moe_num_experts=4, moe_every=2)
        B, S, steps, warmup = 2, 128, 4, 1
    payload = _gpt_train_payload(cfg, B, S, steps, warmup)
    payload["config"]["moe_num_experts"] = cfg.moe_num_experts
    return payload


@register("long_context")
def long_context(mode: str) -> Dict[str, Any]:
    """Ulysses sequence-parallel GPT: activations seq-sharded over the
    ``sp`` axis, heads all-to-all'd inside attention
    (``distributed/sequence_parallel.py``).  Needs ≥4 devices for the
    sp axis — the virtual CPU mesh provides them in smoke mode."""
    import jax

    import paddle_tpu.distributed as dist
    from paddle_tpu.models import gpt_tiny

    sp = 4
    if jax.device_count() < 2 * sp:
        raise RuntimeError(
            f"long_context needs a {2 * sp}-device mesh for the dp×sp "
            f"axes (have {jax.device_count()})")
    if mode == "full":
        cfg = gpt_tiny(hidden_size=512, num_layers=8, num_heads=8,
                       vocab_size=32768, max_position_embeddings=8192,
                       hidden_dropout=0.0, attention_dropout=0.0,
                       sequence_parallel=True)
        B, S, steps, warmup = 2, 8192, 6, 2
    else:
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0,
                       max_position_embeddings=512,
                       sequence_parallel=True)
        B, S, steps, warmup = 2, 512, 4, 1
    topo = dist.CommunicateTopology(["data", "sequence", "model"],
                                    [2, sp, 1])
    dist.set_hybrid_communicate_group(dist.HybridCommunicateGroup(topo))
    try:
        payload = _gpt_train_payload(cfg, B, S, steps, warmup,
                                     shard_data=True)
    finally:
        dist.set_hybrid_communicate_group(None)
    payload["config"]["sp_degree"] = sp
    return payload


def _vision_train_payload(model, B: int, hw: int, steps: int, warmup: int,
                          num_classes: int, channels: int = 3,
                          flops_per_img: float = 0.0) -> Dict[str, Any]:
    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework import random as fw_random
    from paddle_tpu.observability.compilation import track_jit
    from paddle_tpu.observability.mfu import param_count, peak_flops_per_sec

    pt.seed(0)
    model.train()
    trainable = model.trainable_variables()
    rest = {k: v for k, v in model.state_dict().items()
            if k not in trainable}
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                weight_decay=1e-4)
    opt_state = opt.init(trainable)

    def train_step(params, s, x, y, key):
        def loss_fn(tp):
            with fw_random.key_scope(key):
                logits, newv = model.apply({**rest, **tp}, x, mutable=True)
            loss = F.cross_entropy(logits.astype(jnp.float32), y)
            return loss, newv
        (loss, _newv), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_p, new_s = opt.apply_gradients(grads, params, s)
        return loss, new_p, new_s

    jitted = track_jit(jax.jit(train_step, donate_argnums=(0, 1)),
                       name="bench.vision_step",
                       arg_names=("params", "opt_state", "inputs",
                                  "labels", "key"))
    rng = np.random.RandomState(0)

    def make_batch(i):
        x = (rng.randn(B, channels, hw, hw) * 0.5).astype(np.float32)
        y = rng.randint(0, num_classes, (B,)).astype(np.int32)
        return (jnp.asarray(x), jnp.asarray(y))

    x0, y0 = make_batch(0)
    peak = harness.peak_hbm(jitted, trainable, opt_state, x0, y0,
                            jax.random.key(0))
    state = {"p": trainable, "s": opt_state}

    def step_fn(i, batch):
        x, y = batch
        loss, state["p"], state["s"] = jitted(
            state["p"], state["s"], x, y,
            jax.random.fold_in(jax.random.key(0), i))
        return loss

    m = harness.measure_steps(step_fn, make_batch, steps, warmup)
    p50 = harness.pct(sorted(m["step_times_ms"]), 50) or 1.0
    img_s = B / (p50 / 1e3)
    # vision rows keep tokens_per_sec null; img/s lives in extra and the
    # MFU (when a per-image FLOPs figure exists for the config) uses the
    # shared peak definition
    peak = peak_flops_per_sec()
    mfu_val = (img_s * 3.0 * flops_per_img / peak
               if flops_per_img and peak else None)
    return {
        "config": {"batch": B, "hw": hw, "steps": steps,
                   "warmup": warmup,
                   "params_m": param_count(trainable) / 1e6},
        "step_times_ms": m["step_times_ms"],
        "phases_ms": m["phases_ms"],
        "collective_by_op": m.get("collective_by_op"),
        "tokens_per_sec": None,
        "mfu": mfu_val,
        "peak_hbm_bytes": peak,
        "extra": {"images_per_sec": img_s, "warmup_s": m["warmup_s"],
                  "final_loss": m["final_value"]},
    }


@register("resnet")
def resnet(mode: str) -> Dict[str, Any]:
    """BASELINE row #2: ResNet ImageNet-config train step — ResNet-50 at
    224² in full mode (MFU against the 4.089 GFLOPs/img forward cost),
    ResNet-18 at 32² as the CPU smoke."""
    from paddle_tpu.vision.models import resnet18, resnet50
    if mode == "full":
        payload = _vision_train_payload(resnet50(), B=128, hw=224,
                                        steps=10, warmup=3,
                                        num_classes=1000,
                                        flops_per_img=4.089e9)
        payload["config"]["depth"] = 50
    else:
        payload = _vision_train_payload(resnet18(), B=2, hw=32,
                                        steps=3, warmup=1,
                                        num_classes=1000)
        payload["config"]["depth"] = 18
    return payload


@register("mnist")
def mnist(mode: str) -> Dict[str, Any]:
    """LeNet on MNIST-shaped batches — the smallest vision row, mostly a
    canary for per-step host overheads (data/readback dominate)."""
    from paddle_tpu.vision.models import LeNet
    B = 64 if mode == "full" else 16
    steps, warmup = (10, 3) if mode == "full" else (4, 1)
    return _vision_train_payload(LeNet(), B=B, hw=28, steps=steps,
                                 warmup=warmup, num_classes=10,
                                 channels=1)


@register("serve")
def serve(mode: str) -> Dict[str, Any]:
    """Continuous-batching decode through the PR 6 ServingEngine: N
    ragged streams, one interleaved loop.  A bench "step" is one engine
    step (one prefill or one decode batch); TTFT/TPOT percentiles and
    serve-mode (fwd-only) MFU ride in ``extra``."""
    import time as _time

    import numpy as np
    import jax

    import paddle_tpu as pt
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability.mfu import (flops_per_token, mfu,
                                              param_count)
    from paddle_tpu.observability.registry import MetricsRegistry

    n_streams = 8 if mode == "full" else 4
    max_new = 48 if mode == "full" else 12
    cfg = GPTConfig(vocab_size=512,
                    hidden_size=128 if mode == "full" else 64,
                    num_layers=2, num_heads=4,
                    ffn_hidden_size=256 if mode == "full" else 128,
                    max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    pt.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    reg = MetricsRegistry()
    engine = ServingEngine(model, max_seqs=n_streams, kv_block_size=4,
                           registry=reg)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size,
                           rng.randint(3, 8)).tolist()
               for _ in range(n_streams)]
    # warm the prefill/decode compile caches outside the timed window
    engine.generate([p[:3] for p in prompts[:2]], max_new_tokens=2)
    t_warm = _time.perf_counter()
    rids = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    warm_s = _time.perf_counter() - t_warm

    step_ms: List[float] = []
    t0 = _time.perf_counter()
    while engine.has_work() and len(step_ms) < 4096:
        ta = _time.perf_counter()
        engine.step()
        step_ms.append((_time.perf_counter() - ta) * 1e3)
    elapsed = _time.perf_counter() - t0
    results = [engine.collect(r) for r in rids]
    generated = sum(len(r["tokens"]) for r in results)
    tok_s = generated / max(1e-9, elapsed)
    snap = reg.snapshot()

    def hpct(name, p):
        m = snap.get(name)
        return None if not isinstance(m, dict) else m.get(p)

    n_params = param_count(model.trainable_variables())
    flops_tok = flops_per_token(n_params, num_layers=cfg.num_layers,
                                hidden_size=cfg.hidden_size,
                                seq_len=cfg.max_position_embeddings,
                                fwd_only=True)

    def p50(series):
        return harness.pct(sorted(series), 50) or 0.0

    return {
        "config": {"n_streams": n_streams, "max_new_tokens": max_new,
                   "steps": len(step_ms),
                   "params_m": n_params / 1e6,
                   "kv_block_size": engine.cache.block_size},
        "step_times_ms": step_ms,
        # an engine step is dispatch+sample+bookkeeping in one host
        # call; the whole step is the compute phase (sampling syncs
        # internally, so there is no separate readback to time)
        "phases_ms": {"data": 0.0, "compute": p50(step_ms),
                      "readback": 0.0, "collective": 0.0},
        "tokens_per_sec": tok_s,
        "mfu": mfu(tok_s, flops_tok),
        "peak_hbm_bytes": harness.peak_hbm(),
        "extra": {"generated_tokens": generated,
                  "engine_steps": len(step_ms),
                  "warmup_s": warm_s,
                  "ttft_ms_p50": hpct("serve.ttft_ms", "p50"),
                  "ttft_ms_p99": hpct("serve.ttft_ms", "p99"),
                  "tpot_ms_p50": hpct("serve.tpot_ms", "p50"),
                  "tpot_ms_p99": hpct("serve.tpot_ms", "p99"),
                  "preemptions": engine.sched.preemptions,
                  # real-vs-padded token slots (ISSUE 19): pow2 prefill
                  # buckets + fixed decode batch; feeds the roofline
                  # padding sink so pad rows stop inflating serve MFU
                  "padding_frac": round(engine.padding_frac(), 6)},
    }


@register("serve_fleet")
def serve_fleet(mode: str) -> Dict[str, Any]:
    """Routed decode through the ISSUE 16 fleet: two in-process
    replicas behind the Router, one mid-run failover.  A bench "step"
    is one router pump (poll + step every live replica); the timed
    window includes journal replay of the failed-over streams, so the
    figure prices what resilience costs, not just the happy path.

    Runs twice (ISSUE 18): once with request tracing OFF (the parity
    baseline) and once ON (the reported pass).  ``extra`` carries the
    assembled trace coverage, the per-component breakdown medians and
    both passes' step p50, so a gross cost of tracing stays visible."""
    import os as _os
    import time as _time

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.inference.fleet import LocalReplica, Router
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import requesttrace
    from paddle_tpu.observability.mfu import (flops_per_token, mfu,
                                              param_count)
    from paddle_tpu.observability.registry import MetricsRegistry

    n_streams = 8 if mode == "full" else 4
    max_new = 48 if mode == "full" else 24
    cfg = GPTConfig(vocab_size=512,
                    hidden_size=128 if mode == "full" else 64,
                    num_layers=2, num_heads=4,
                    ffn_hidden_size=256 if mode == "full" else 128,
                    max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0)

    def build_engine(reg, i):
        pt.seed(0)                    # identical weights per replica
        model = GPTForCausalLM(cfg)
        model.eval()
        return model, ServingEngine(model, max_seqs=n_streams,
                                    kv_block_size=4, registry=reg,
                                    replica_id=i)

    class _ListSink:                  # in-memory trace record capture
        def __init__(self):
            self.records: List[Dict[str, Any]] = []

        def write(self, rec):
            self.records.append(rec)

        def flush(self):
            pass

        def close(self):
            pass

    def run_pass(traced: bool) -> Dict[str, Any]:
        prev = _os.environ.get(requesttrace.TRACE_REQUESTS_ENV)
        _os.environ[requesttrace.TRACE_REQUESTS_ENV] = \
            "1" if traced else "0"
        try:
            reg = MetricsRegistry()
            sink = _ListSink()
            if traced:
                reg.add_sink(sink)
            models, replicas = [], []
            for i in range(2):
                model, eng = build_engine(reg, i)
                models.append(model)
                replicas.append(LocalReplica(eng, replica_id=i))
            router = Router(replicas, registry=reg)
            rng = np.random.RandomState(7)
            prompts = [rng.randint(1, cfg.vocab_size,
                                   rng.randint(3, 8)).tolist()
                       for _ in range(n_streams)]
            # warm both replicas' compile caches outside the timed
            # window — untraced, so assembled traces == client streams
            _os.environ[requesttrace.TRACE_REQUESTS_ENV] = "0"
            for r in replicas:
                r.engine.generate([prompts[0][:3]], max_new_tokens=2)
            _os.environ[requesttrace.TRACE_REQUESTS_ENV] = \
                "1" if traced else "0"
            rids = [router.submit(p, max_new_tokens=max_new)
                    for p in prompts]

            kill_after = 3            # pumps before the failover drill
            step_ms: List[float] = []
            t0 = _time.perf_counter()
            while len(step_ms) < 4096:
                ta = _time.perf_counter()
                live = router.pump()
                step_ms.append((_time.perf_counter() - ta) * 1e3)
                if len(step_ms) == kill_after:
                    victim = next((j.replica_id
                                   for j in router.journals.values()
                                   if not j.finished
                                   and j.replica_id is not None), None)
                    if victim is not None:
                        replicas[victim].engine._state = "stopped"
                if live == 0:
                    break
            elapsed = _time.perf_counter() - t0
            results = [router.collect(r, timeout=60) for r in rids]
            return {"step_ms": step_ms, "elapsed": elapsed,
                    "generated": sum(len(r["tokens"]) for r in results),
                    "records": sink.records, "router": router,
                    "models": models, "n_requests": len(rids),
                    "engines": [r.engine for r in replicas]}
        finally:
            if prev is None:
                _os.environ.pop(requesttrace.TRACE_REQUESTS_ENV, None)
            else:
                _os.environ[requesttrace.TRACE_REQUESTS_ENV] = prev

    def p50(series):
        return harness.pct(sorted(series), 50) or 0.0

    base = run_pass(traced=False)     # parity baseline: same token
    run = run_pass(traced=True)       # count, untraced step p50
    step_ms = run["step_ms"]
    generated = run["generated"]
    tok_s = generated / max(1e-9, run["elapsed"])
    p50_off, p50_on = p50(base["step_ms"]), p50(step_ms)

    asm = requesttrace.TraceAssembler().from_records(run["records"])
    traces = asm["traces"]
    coverages = sorted(t["coverage"] for t in traces)
    comps = sorted({c for t in traces for c in t["components"]})
    comp_medians = {
        c: round(harness.pct(sorted(t["components"].get(c, 0.0)
                                    for t in traces), 50) or 0.0, 3)
        for c in comps}
    attrib = requesttrace.tail_latency_attribution(traces)

    n_params = param_count(run["models"][0].trainable_variables())
    flops_tok = flops_per_token(n_params, num_layers=cfg.num_layers,
                                hidden_size=cfg.hidden_size,
                                seq_len=cfg.max_position_embeddings,
                                fwd_only=True)
    router = run["router"]
    # fleet-wide padding: pooled real/slot counts across both replicas
    pad_real = sum(e._pad_real_tokens for e in run["engines"])
    pad_slots = sum(e._pad_slot_tokens for e in run["engines"])
    padding_frac = (1.0 - pad_real / pad_slots) if pad_slots else 0.0

    return {
        "config": {"n_streams": n_streams, "max_new_tokens": max_new,
                   "replicas": 2, "steps": len(step_ms),
                   "params_m": n_params / 1e6},
        "step_times_ms": step_ms,
        # a pump is poll+step+journal in one host call — all compute
        # phase (no separate data/readback to time at this layer)
        "phases_ms": {"data": 0.0, "compute": p50(step_ms),
                      "readback": 0.0, "collective": 0.0},
        "tokens_per_sec": tok_s,
        "mfu": mfu(tok_s, flops_tok),
        "peak_hbm_bytes": harness.peak_hbm(),
        "extra": {"generated_tokens": generated,
                  "router_pumps": len(step_ms),
                  "failovers": router.failovers,
                  "dispatches": run["n_requests"] + router.failovers,
                  "trace_step_p50_off_ms": round(p50_off, 3),
                  "trace_step_p50_on_ms": round(p50_on, 3),
                  "traces_assembled": len(traces),
                  "traces_complete": asm["complete"],
                  "trace_orphan_spans": len(asm["orphan_spans"]),
                  "trace_coverage_p50": round(
                      harness.pct(coverages, 50) or 0.0, 4),
                  "trace_coverage_min": round(
                      coverages[0] if coverages else 0.0, 4),
                  "trace_component_median_ms": comp_medians,
                  "tail_dominant": (attrib or {}).get("dominant"),
                  "padding_frac": round(padding_frac, 6)},
    }
