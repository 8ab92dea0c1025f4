#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process drives the system's two entry points at the full width and depth
of GPT-125M, bf16, random weights from a seed:

- **train**: ``GPTForCausalLM`` + ``pt.optimizer.AdamW`` + amp O1 in one
  donated ``jax.jit`` step at B=8, S=2048 on a fixed seeded batch — loss
  finite and falling, the flash kernels in the compiled program, the step
  timed ending in ``jax.block_until_ready`` and ending in ``float(loss)``;
- **serve**: ``paddle_tpu.inference.ServingEngine`` (max_seqs=8,
  max_model_len=1024, default kv_block_size, greedy) over eight requests in
  three prefill buckets, 32 new tokens each — every request finishes, nothing
  poisoned, restarted or leaked, the paged kernel in the compiled decode
  program, and its logits agree with a second engine forced onto
  ``paged_attention_reference``;
- **multichip** (only with >= 4 devices): GPT-1.3B through ``fleet.init`` →
  ``distributed_model`` → ``distributed_optimizer`` on a dp=2 x mp=2 mesh
  with ZeRO-1 and recompute — parameters placed as their specs say, memory
  spread over the devices, the strategy's collectives in the HLO.

It fails (non-zero, no result line) when jax finds no TPU.  ``--tiny`` is the
explicit CPU rehearsal of the same code at toy widths: it says so, and it
prints no time, rate or other device number.  Any leg's exception is the
exit status; nothing is caught.  The legs' results go out on a ``summary:``
line ending ``"claim": null``; the last stdout line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import collections
import gc
import glob
import json
import os
import re
import shutil
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# bf16 keeps 8 significand bits: one ulp is 2**-6 for 2 <= |x| < 4, where
# the largest logits of a randomly initialised GPT sit.  Both decode routes
# read the same bf16 pages and accumulate in f32, so their attention outputs
# differ only by summation order before the cast back to bf16 — an ulp here
# and there, carried through 12 layers and the bf16 tied-head matmul.  Four
# ulp bounds that; a kernel that dropped a block, a head or the length mask
# moves logits by O(1).
LOGIT_ATOL = 4 * 2.0 ** -6

FULL = {
    "train": dict(batch=8, seq=2048, warmup=3, steps=5),
    "serve": dict(max_seqs=8, max_model_len=1024, new_tokens=32,
                  prompt_lens=(24, 29, 32, 100, 117, 128, 400, 487)),
    # every width of GPT-1.3B; depth cut 24 -> 16.  AdamW under ZeRO-1 keeps
    # three fp32 flat copies of ALL parameters sharded over dp alone (not
    # dp x mp), and its GSPMD path assembles the whole fp32 flat gradient on
    # every device: 24 layers want 17.7 GB of a chip's 15.75 at any batch
    # (the compiler's count; ROADMAP A6).  Batch does not move that number,
    # so depth is the cut.
    "multi": dict(batch=4, seq=2048, steps=4, num_layers=16),
}
TINY = {
    "train": dict(batch=2, seq=128, warmup=1, steps=2),
    "serve": dict(max_seqs=4, max_model_len=128, new_tokens=6,
                  prompt_lens=(5, 20, 28, 50)),
    "multi": dict(batch=4, seq=128, steps=3, num_layers=2),
}


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    """A failed check is the exit status (assert is stripped under -O)."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq", "flash_decode",
           "paged_decode", "fused_ln_linear", "fused_linear_residual",
           "fused_ffn")


def kernels_in(hlo_text: str) -> collections.Counter:
    """Pallas kernels compiled by Mosaic into this program, by the stable
    ``name=`` each ``pallas_call`` carries (autodiff decorates it:
    ``jvp_flash_fwd_``).  An interpret-mode kernel lowers to plain HLO and
    leaves no ``tpu_custom_call``."""
    found = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            head = line.split(" = ", 1)[0]
            found[next((k for k in KERNELS if k in head), "unnamed")] += 1
    return found


def collectives_in(hlo_text: str) -> collections.Counter:
    return collections.Counter(re.findall(
        r"= [^\n=]*? (all-reduce|reduce-scatter|all-gather|all-to-all|"
        r"collective-permute)(?:-start)?\(", hlo_text))


def spread(samples):
    s = sorted(samples)
    return {"min": round(s[0], 3), "median": round(s[len(s) // 2], 3),
            "max": round(s[-1], 3), "n": len(s)}


def gpt_config(tiny: bool, big: bool = False, **kw):
    from paddle_tpu.models import gpt_125m, gpt_1p3b, gpt_tiny
    common = dict(dtype="bfloat16", use_pallas_attention=True,
                  hidden_dropout=0.0, attention_dropout=0.0, **kw)
    if tiny:
        # heads of 32 keep head_dim a multiple of 8 for the kernels
        return gpt_tiny(max_position_embeddings=256, **common)
    if big:
        return gpt_1p3b(**common)
    return gpt_125m(max_position_embeddings=2048, **common)


def seeded_batch(vocab: int, batch: int, seq: int):
    import numpy as np
    return np.random.RandomState(0).randint(0, vocab, (batch, seq),
                                            dtype=np.int32)


def make_train_step(model, opt):
    import jax
    from paddle_tpu import amp
    from paddle_tpu.framework import random as fw_random

    def train_step(params, opt_state, ids, key):
        def loss_fn(p):
            with fw_random.key_scope(key):
                with amp.auto_cast(level="O1", dtype="bfloat16"):
                    # labels = inputs: the model shifts by one internally
                    loss, _ = model.apply(p, ids, labels=ids)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.apply_gradients(grads, params, opt_state)
        return loss, params, opt_state

    return jax.jit(train_step, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# leg 1: the trainer
# ---------------------------------------------------------------------------
def train_leg(size, tiny: bool):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import GPTForCausalLM

    cfg = gpt_config(tiny)
    pt.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    params = model.state_dict()
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01)
    opt_state = opt.init(params)
    ids = jnp.asarray(seeded_batch(cfg.vocab_size, size["batch"],
                                   size["seq"]))
    key = jax.random.key(0)

    t0 = time.perf_counter()
    step = make_train_step(model, opt).lower(
        params, opt_state, ids, key).compile()
    compile_s = time.perf_counter() - t0
    kernels = kernels_in(step.as_text())

    losses = []
    keys = iter(jax.random.split(key, size["warmup"] + 2 * size["steps"]))

    def run(n, sync):
        nonlocal params, opt_state
        times = []
        for _ in range(n):
            k = next(keys)
            t = time.perf_counter()
            loss, params, opt_state = step(params, opt_state, ids, k)
            sync(loss)
            times.append((time.perf_counter() - t) * 1e3)
            losses.append(float(loss))
        return times

    t0 = time.perf_counter()
    run(size["warmup"], jax.block_until_ready)
    warmup_s = time.perf_counter() - t0
    block_ms = run(size["steps"], jax.block_until_ready)
    float_ms = run(size["steps"], float)

    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"train: nonfinite loss in {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall: {losses[0]} -> {losses[-1]}")
    out = {"params_m": round(sum(v.size for v in params.values()) / 1e6, 1),
           "batch": size["batch"], "seq": size["seq"],
           "loss_first": round(losses[0], 4),
           "loss_last": round(losses[-1], 4), "steps": len(losses),
           "kernels": dict(kernels)}
    if not tiny:
        for k in ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"):
            check(kernels[k] >= cfg.num_layers,
                  f"train: {k} not in the compiled step ({dict(kernels)})")
        out.update(compile_s=round(compile_s, 1),
                   warmup_s=round(warmup_s, 2),
                   step_ms_block_until_ready=spread(block_ms),
                   step_ms_float_loss=spread(float_ms))
    say(f"train: {json.dumps(out)}")
    return out


# ---------------------------------------------------------------------------
# leg 2: the server
# ---------------------------------------------------------------------------
def serve_once(model, size, prompts, route: str, hlo_dir: str, passes: int):
    """Build an engine on ``route`` ('pallas' | 'reference' | '' = the
    backend's own choice), run the requests ``passes`` times (cold, then
    steady), return what came out."""
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.observability.registry import MetricsRegistry

    # both knobs are read when the engine traces its step
    os.environ["PTPU_PAGED_KERNEL"] = route
    os.environ["PTPU_HLO_DUMP_DIR"] = hlo_dir
    shutil.rmtree(hlo_dir, ignore_errors=True)    # an earlier run's dumps
    eng = ServingEngine(model, max_seqs=size["max_seqs"],
                        max_model_len=size["max_model_len"],
                        capture_logits=True, registry=MetricsRegistry())
    runs = []
    for _ in range(passes):
        rids = [eng.submit(p, max_new_tokens=size["new_tokens"])
                for p in prompts]
        t0 = time.perf_counter()
        steps = eng.run()
        wall = time.perf_counter() - t0
        runs.append({"wall_s": wall, "steps": steps,
                     "results": [eng.collect(r) for r in rids]})
    stats = eng.stats()     # kv_blocks.leaked is cache.leak_report()'s count
    eng.stop()
    decode_hlo = "".join(
        open(f).read()
        for f in glob.glob(os.path.join(hlo_dir, "serve_decode-*.compiled.txt")))
    return runs, stats, kernels_in(decode_hlo)


def serve_leg(size, tiny: bool):
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.inference.scheduler import prefill_bucket
    from paddle_tpu.models import GPTForCausalLM

    cfg = gpt_config(tiny)
    pt.seed(0)
    model = GPTForCausalLM(cfg)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in size["prompt_lens"]]
    buckets = sorted({prefill_bucket(n, size["max_model_len"])
                      for n in size["prompt_lens"]})
    check(len(buckets) == 3, f"serve: want 3 prefill buckets, got {buckets}")

    # the kernel route: the backend's own choice on the chip; the rehearsal
    # asks for it by name (interpret mode), since a CPU defaults to the
    # reference and would compare it with itself
    (cold, steady), stats, kernels = serve_once(
        model, size, prompts, "pallas" if tiny else "",
        os.path.join(OUT_DIR, "hlo_pallas"), passes=2)
    (reference,), _, ref_kernels = serve_once(
        model, size, prompts, "reference",
        os.path.join(OUT_DIR, "hlo_reference"), passes=1)

    n_new = size["new_tokens"]
    for run in (cold, steady, reference):
        for r in run["results"]:
            check(len(r["tokens"]) == n_new
                  and r["finish_reason"] == "max_new_tokens",
                  f"serve: {r['request_id']} ended {r['finish_reason']} "
                  f"with {len(r['tokens'])}/{n_new} tokens")
    res, leaked = stats["resilience"], stats["kv_blocks"]["leaked"]
    check(res["poisoned"] == 0 and res["watchdog_restarts"] == 0
          and leaked == 0 and stats["kv_blocks"]["balanced"],
          f"serve: poisoned={res['poisoned']} "
          f"restarts={res['watchdog_restarts']} leaked={leaked}")
    check([r["tokens"] for r in cold["results"]]
          == [r["tokens"] for r in steady["results"]],
          "serve: greedy tokens differ between two passes of one engine")

    # logits, not tokens: with random weights the largest logit changes on
    # rounding, after which the two streams are different requests.  Compare
    # every position both routes reached with the same history.
    worst, compared, diverged = 0.0, 0, 0
    for a, b in zip(steady["results"], reference["results"]):
        same = 0
        while same < n_new and a["tokens"][:same] == b["tokens"][:same]:
            d = float(np.max(np.abs(a["logits"][same] - b["logits"][same])))
            check(np.isfinite(a["logits"][same]).all(),
                  f"serve: nonfinite logits for {a['request_id']}")
            worst, compared, same = max(worst, d), compared + 1, same + 1
        check(same >= 2, f"serve: {a['request_id']} has no decode position "
              f"to compare")
        diverged += a["tokens"] != b["tokens"]
    check(worst <= LOGIT_ATOL,
          f"serve: paged kernel vs reference logits differ by {worst} "
          f"(> {LOGIT_ATOL}) over {compared} positions")

    out = {"requests": len(prompts), "new_tokens": n_new,
           "kv_block_size": stats["kv_block_size"],
           "prefill_buckets": buckets, "steps": steady["steps"],
           "poisoned": res["poisoned"],
           "watchdog_restarts": res["watchdog_restarts"],
           "leaked_blocks": leaked,
           "logits_max_abs_diff_vs_reference": round(worst, 5),
           "logit_atol": LOGIT_ATOL, "positions_compared": compared,
           "streams_diverged_after_rounding": int(diverged),
           "kernels_decode": dict(kernels),
           "kernels_decode_reference_route": dict(ref_kernels)}
    if not tiny:
        check(kernels["paged_decode"] >= cfg.num_layers,
              f"serve: paged_decode not in the compiled decode step "
              f"({dict(kernels)})")
        check(not ref_kernels["paged_decode"],
              "serve: the reference route compiled the paged kernel")
        tpot = [r["tpot_ms"] for r in steady["results"]]
        ttft = [r["ttft_ms"] for r in steady["results"]]
        out.update(compile_and_warmup_s=round(cold["wall_s"], 1),
                   steady_wall_s=round(steady["wall_s"], 2),
                   steady_step_ms_mean=round(
                       steady["wall_s"] * 1e3 / steady["steps"], 2),
                   tpot_ms=spread(tpot), ttft_ms=spread(ttft))
    say(f"serve: {json.dumps(out)}")
    return out


# ---------------------------------------------------------------------------
# leg 3: four chips through the hybrid path
# ---------------------------------------------------------------------------
def multichip_leg(size, tiny: bool):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mp_layers import param_sharding
    from paddle_tpu.models import GPTForCausalLM

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1}
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 1, "shard_weight_update": True}
    strategy.recompute = True
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_mesh()
    devices = list(mesh.devices.flat)
    say(f"multichip: mesh {dict(mesh.shape)} over "
        f"{[d.id for d in devices]}")

    cfg = gpt_config(tiny, big=True, num_layers=size["num_layers"])
    pt.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    model = fleet.distributed_model(model)
    # what a first multi-chip run gets wrong, 1: parameters left where the
    # initialiser built them instead of where their spec says
    for name, p in model.named_parameters():
        want = param_sharding(p, mesh)
        got = p.value.sharding
        check(got.is_equivalent_to(want, p.value.ndim)
              and got.device_set == set(devices),
              f"multichip: {name} is placed {got}, its spec says {want}")
    params = model.state_dict()
    opt = fleet.distributed_optimizer(
        pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01))
    opt_state = opt.init(params)
    ids = dist.shard_batch(jnp.asarray(seeded_batch(
        cfg.vocab_size, size["batch"], size["seq"])))
    key = jax.random.key(0)

    t0 = time.perf_counter()
    step = make_train_step(model, opt).lower(
        params, opt_state, ids, key).compile()
    compile_s = time.perf_counter() - t0
    hlo = step.as_text()
    kernels, colls = kernels_in(hlo), collectives_in(hlo)
    # 2: a strategy whose collectives never reached the program.  dp grads
    # and mp activations reduce; ZeRO-1 scatters grads to the state shards
    # and gathers the updated parameters back
    check(colls["all-reduce"] > 0 and colls["all-gather"] > 0
          and (colls["reduce-scatter"] > 0 or colls["all-to-all"] > 0
               or colls["collective-permute"] > 0),
          f"multichip: collectives in the HLO: {dict(colls)}")

    placed = {k: v.sharding for k, v in params.items()}
    losses, times = [], []
    for k in jax.random.split(key, size["steps"]):
        t = time.perf_counter()
        loss, params, opt_state = step(params, opt_state, ids, k)
        losses.append(float(loss))
        times.append((time.perf_counter() - t) * 1e3)
    # 3: a step that hands the parameters back in another layout than it
    # got them (the compiled step would refuse its own output next call;
    # a plain jit would quietly compile a second program)
    moved = [k for k, v in params.items()
             if not v.sharding.is_equivalent_to(placed[k], v.ndim)]
    check(not moved, f"multichip: the step re-laid-out {len(moved)} "
          f"parameters, e.g. {moved[:3]}")
    check(all(l == l and abs(l) != float("inf") for l in losses)
          and losses[-1] < losses[0],
          f"multichip: loss series {losses}")

    out = {"mesh": dict(mesh.shape), "devices": len(devices),
           "params_m": round(sum(v.size for v in params.values()) / 1e6, 1),
           "num_layers": cfg.num_layers,
           "batch": size["batch"], "seq": size["seq"],
           "losses": [round(l, 4) for l in losses],
           "collectives": dict(colls),
           # the route attention took under the mesh (ROADMAP C6)
           "attention_route": "flash" if kernels["flash_fwd"] else "xla",
           "kernels": dict(kernels)}
    if not tiny:
        # 4: everything on device 0
        used = [d.memory_stats()["bytes_in_use"] for d in devices]
        check(max(used) <= 1.25 * min(used),
              f"multichip: bytes_in_use uneven across devices: {used}")
        out.update(compile_s=round(compile_s, 1),
                   step_ms=spread(times[1:]),
                   gib_in_use_per_device=[round(u / 2 ** 30, 2)
                                          for u in used])
    say(f"multichip: {json.dumps(out)}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy widths; prints no device "
                         "number and proves nothing about the chip")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if not args.tiny and dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (jax found {dev.platform!r}); this run "
            f"proves nothing.  --tiny is the CPU rehearsal.")
    # (a directory without the package fails here, before any output)
    from paddle_tpu.observability import get_registry
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind!r}"
        f" devices={device['count']} jax={jax.__version__}")
    if args.tiny:
        say("chip_smoke: --tiny REHEARSAL — toy widths, interpret-mode "
            "kernels; times and rates: not measured")
    size = TINY if args.tiny else FULL

    from paddle_tpu.observability.compilecache import enable_persistent_cache
    from paddle_tpu.observability.mfu import device_spec
    say(f"chip_smoke: compile cache at {enable_persistent_cache()}")
    spec = device_spec()
    check(args.tiny or spec["known"],
          f"device_kind {dev.device_kind!r} is not in "
          f"observability.mfu.DEVICE_SPECS")
    say(f"chip_smoke: device spec {spec}")

    summary = {"jax": jax.__version__, "rehearsal": args.tiny}
    summary["train"] = train_leg(size["train"], args.tiny)
    gc.collect()
    summary["serve"] = serve_leg(size["serve"], args.tiny)
    gc.collect()
    if device["count"] >= 4:
        summary["multichip"] = multichip_leg(size["multi"], args.tiny)
    else:
        say(f"multichip: not run ({device['count']} device)")
        summary["multichip"] = f"not run ({device['count']} device)"
    reg = get_registry()
    summary["persistent_cache"] = {
        "hits": int(reg.counter("compile.persistent_cache_hits").value),
        "requests": int(
            reg.counter("compile.persistent_cache_requests").value)}
    summary["claim"] = None
    say(f"summary: {json.dumps(summary)}")
    # every check above raised on failure, so reaching here is the pass
    say(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
