"""Builder of the Nemotron-3-Super configurations: the one place where the
benchmark touches ``paddle_tpu``'s Nemotron-H.  ``configs/<name>.json`` names
it as ``"entry": "nemotron_h:build"``; the serving job sees only the methods
below (``jobs/serve.py`` is unchanged).

The configuration file holds the catalog row's keys as published; the
``deployment`` keys say which share of each layer this chip holds:
``n_routed_experts`` counts the experts HELD (the router keeps
``n_routed_experts x ep_degree``), ``vocab_size`` the rows of the slice.

The traffic file's ``engine`` block gives ``num_kv_blocks`` a layer KIND:
``{"full": .., "state": ..}``, the attention layer's pages and the Mamba
layers' state slots, a slot a sequence (``paddle_tpu/inference/
kv_cache.py``).

``reference_logits_fn`` settles the router's near-ties as the other expert
builders do: under ``capture_logits`` the engine hands out the experts it
chose for every token of the checked sequences (``per_token``); the
reference checks each against its own float32 scores and computes under
them, within the two limits of the configuration file.

It also holds the recurrent state to the precision the configuration states
(``assumed.float32_state``), which the logits cannot see: a state kept in
bf16 between steps reads in them as bf16 weights and activations do.  Each
checked prompt goes through the engine twice more, alone: once to the end
of its prefill and once through the check's decode steps, and each time its
slot's float32 state is read in every Mamba layer.  What the decode steps
added, ``dS = S_t1 - S_t0``, is compared head by head with the reference's
own under the engine's routing: ``|dS_engine - dS_ref| / |dS_ref|``.  A
head that keeps most of its state over the steps (``exp(A sum dt)`` at
least ``SLOW``) carries a state far larger than what the steps add, so a
state rounded at every step shows there at once, while the rounding of the
engine's inputs, which moves ``dS`` by its own share, does not grow.
``state_step_error`` is, of each sequence and Mamba layer, the median of
that ratio over its slow heads, and the largest of them; it must lie under
``state_step_tolerance`` (configuration file), or the logits turn to NaN.
"""
from __future__ import annotations

import json
from typing import Any, Dict

import numpy as np

LIMITS = ("routing_tie_eps", "routing_differ_share", "state_step_tolerance")
# a head that keeps at least this share of its state over the check's decode
# steps is a slow one (``state_step_error``)
SLOW = 0.9


class NemotronHSystem:
    def __init__(self, config: Dict[str, Any], seed: int):
        self.config = config
        self.seed = int(seed)
        self.model = self.engine = None
        self.check_report: Dict[str, Any] = {}
        # of the last check, a sequence and Mamba layer a row, a head a
        # column: the decode steps' error, their final state's, what each
        # head kept, its state over what the steps added
        # (``state_step_error``; tools read them)
        self.state_readings: Dict[str, np.ndarray] = {}
        # the reference, built on the first check and kept: its programs
        # are traced once a process (a tool that swaps the reference's
        # casts sets this back to None)
        self.reference = None

    # -- sizes, for the benchmark's arithmetic ------------------------------
    @property
    def shape(self) -> Dict[str, int]:
        c = self.config
        pattern = c["hybrid_override_pattern"]
        return {"layers": c["num_hidden_layers"], "hidden": c["hidden_size"],
                "heads": c["num_attention_heads"], "vocab": c["vocab_size"],
                "qk_head_dim": c["head_dim"], "v_head_dim": c["head_dim"],
                "full_layers": pattern.count("*"),
                "full_kv_heads": c["num_key_value_heads"],
                "expert_layers": pattern.count("E"),
                "experts_held": c["n_routed_experts"],
                "expert_width": c["moe_intermediate_size"],
                "expert_io_width": c["moe_latent_size"],
                "mamba_layers": pattern.count("M"),
                "mamba_heads": c["mamba_num_heads"],
                "mamba_head_dim": c["mamba_head_dim"],
                "ssm_state": c["ssm_state_size"],
                "ssm_groups": c["n_groups"],
                "positions": c["max_position_embeddings"]}

    # -- the model -------------------------------------------------------------
    def _model_config(self):
        from paddle_tpu.models.nemotron_h import NemotronHConfig
        c = self.config
        pattern = c["hybrid_override_pattern"]
        if (c["model_type"] != "nemotron_h"
                or len(pattern) != c["num_hidden_layers"]
                or c["mlp_hidden_act"] != "relu2"
                or c["mamba_hidden_act"] != "silu"
                or c["n_group"] != 1 or c["topk_group"] != 1
                or c["n_shared_experts"] != 1 or c["tie_word_embeddings"]
                or c["attention_bias"] or c["mlp_bias"] or c["use_bias"]
                or c["mamba_proj_bias"] or not c["use_conv_bias"]
                or c["residual_in_fp32"] or c["sliding_window"] is not None
                or c["num_nextn_predict_layers"]
                or c["expand"] * c["hidden_size"]
                != c["mamba_num_heads"] * c["mamba_head_dim"]
                or c["layer_norm_epsilon"] != c["norm_eps"]):
            raise ValueError("a Nemotron-H this builder cannot build")
        return NemotronHConfig(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            hybrid_override_pattern=pattern,
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            mamba_num_heads=c["mamba_num_heads"],
            mamba_head_dim=c["mamba_head_dim"], n_groups=c["n_groups"],
            ssm_state_size=c["ssm_state_size"],
            conv_kernel=c["conv_kernel"], chunk_size=c["chunk_size"],
            moe_intermediate_size=c["moe_intermediate_size"],
            moe_latent_size=c["moe_latent_size"],
            moe_shared_expert_intermediate_size=c[
                "moe_shared_expert_intermediate_size"],
            n_routed_experts=c["n_routed_experts"] * c["ep_degree"],
            n_shared_experts=c["n_shared_experts"],
            num_experts_per_tok=c["num_experts_per_tok"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            scoring_func="sigmoid",
            routed_scaling_factor=c["routed_scaling_factor"],
            norm_topk_prob=c["norm_topk_prob"],
            rms_norm_eps=c["layer_norm_epsilon"],
            time_step_min=c["time_step_min"],
            time_step_max=c["time_step_max"],
            time_step_floor=c["time_step_floor"],
            max_position_embeddings=c["max_position_embeddings"],
            initializer_range=c["initializer_range"],
            dtype=c["serve_weights_dtype"], ep_degree=c["ep_degree"],
            ep_rank=c["ep_rank"])

    def build_for_serving(self, engine_args: Dict[str, Any]):
        """``ServingEngine`` over the model, every tensor made in the type
        it is served in."""
        import paddle_tpu as pt
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.models.nemotron_h import NemotronHForCausalLM
        from paddle_tpu.observability.compilecache import (
            enable_persistent_cache)
        from paddle_tpu.observability.registry import MetricsRegistry
        enable_persistent_cache()
        pt.seed(self.seed % (2 ** 31 - 1))
        self.model = NemotronHForCausalLM(self._model_config())
        self.registry = MetricsRegistry()
        self.engine = ServingEngine(
            self.model, max_seqs=engine_args["max_seqs"],
            max_model_len=engine_args["max_model_len"],
            kv_block_size=engine_args.get("kv_block_size"),
            num_kv_blocks=engine_args["num_kv_blocks"],
            capture_logits=False, registry=self.registry)
        return self.engine

    # -- the plain reference's view of the program's parameters -------------
    def reference_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        g = lambda k: params[k]          # noqa: E731
        layers = []
        for i, kind in enumerate(self.config["hybrid_override_pattern"]):
            p = f"layers.{i}."
            layer = {"kind": kind, "norm": g(p + "norm.weight")}
            if kind == "M":
                m = p + "mixer."
                layer.update(w_in=g(m + "w_in"), conv_w=g(m + "conv_w"),
                             conv_b=g(m + "conv_b"),
                             dt_bias=g(m + "dt_bias"), A_log=g(m + "A_log"),
                             D=g(m + "D"), gate_norm=g(m + "norm"),
                             w_out=g(m + "w_out"))
            elif kind == "*":
                a = p + "mixer."
                layer.update(q=g(a + "q"), k=g(a + "k"), v=g(a + "v"),
                             o=g(a + "o"))
            else:
                m = p + "mlp."
                layer.update(router=g(m + "router"),
                             router_bias=g(m + "router_bias"),
                             latent_down=g(m + "latent_down"),
                             latent_up=g(m + "latent_up"),
                             w_up=g(m + "w_up"), w_down=g(m + "w_down"),
                             shared_up=g(m + "shared.w_up"),
                             shared_down=g(m + "shared.w_down"))
            layers.append(layer)
        return {"embed": g("embed"), "head": g("head"),
                "norm": g("norm.weight"), "layers": layers}

    def _reference_cfg(self) -> Dict[str, Any]:
        c = dict(self.config)
        c["n_routed_experts"] = (self.config["n_routed_experts"]
                                 * self.config["ep_degree"])
        return c

    def _captured(self, ids, lengths, prompt_lens):
        """What the engine handed out for the checked sequences, found by
        their prompts among the finished requests that captured: the
        experts of every cached token ``(expert layers, n, k)``."""
        captured = [s for s in self.engine.sched.finished.values()
                    if s.capture_logits and s.per_token]
        routing = []
        for row, n, p in zip(np.asarray(ids), lengths, prompt_lens):
            match = [s for s in captured
                     if list(s.prompt) == row[:p].tolist()]
            if not match:
                raise LookupError("no captured choices for a checked "
                                  "sequence: the engine handed none out")
            chosen = np.concatenate(
                [c["moe_topk"] for c in match[-1].per_token])
            routing.append(np.swapaxes(chosen, 0, 1)[:, :n])
        return routing

    def reference_logits_fn(self):
        """``fn(params, ids, positions) -> logits``: the reference's
        forward pass under the engine's checked choices."""
        from perfbench.reference import nemotron_h as ref
        if self.reference is None:
            self.reference = ref.Reference(self._reference_cfg())
        reference = self.reference
        limits = {k: float(self.config[k]) for k in LIMITS}

        def fn(params, ids, positions):
            positions = np.asarray(positions)
            lengths = positions[:, -1] + 1
            routing = self._captured(ids, lengths, positions[:, 0] + 1)
            logits, report = reference.logits_at(
                params, ids, positions, lengths, routing, limits)
            state = self._state_check(reference, params, np.asarray(ids),
                                      positions)
            state["ok"] = (state["state_step_error"]
                           <= limits["state_step_tolerance"])
            report = dict(report, ok=report["ok"] and state["ok"],
                          state=state)
            if not report["ok"]:
                logits = np.full_like(logits, np.nan)
            self.check_report = dict(report, limits=limits)
            print("choices_check: " + json.dumps(self.check_report),
                  flush=True)
            return logits

        return fn

    # -- the recurrent state -------------------------------------------------
    def _probe(self, prompt, new: int):
        """``prompt`` through the engine alone, to the end of its prefill and
        then through ``new`` tokens: each Mamba layer's state of its slot
        both times, the tokens and the experts the engine chose."""
        from paddle_tpu.inference.kv_cache import STATE
        engine, cache = self.engine, self.engine.cache
        layers = [i for i, k in enumerate(cache.layer_kinds) if k == STATE]
        slot_of = {}
        free = cache.free_seq

        def noted(seq_id, *args, **kw):
            # the slot a finished probe held: untouched until a new
            # sequence takes it
            table = cache.table(seq_id, STATE)
            if table:
                slot_of[seq_id] = table[0]
            free(seq_id, *args, **kw)

        def states(rid):
            return [np.asarray(cache.pages[i][1][slot_of[rid]])
                    for i in layers]

        cache.free_seq, engine.capture_logits = noted, True
        try:
            rid = engine.submit(prompt, max_new_tokens=1)
            engine.run()
            first = states(rid)
            rid = engine.submit(prompt, max_new_tokens=new)
            engine.run()
            last = states(rid)
        finally:
            del cache.free_seq
            engine.capture_logits = False
        seq = engine.sched.finished[rid]
        chosen = np.concatenate([c["moe_topk"] for c in seq.per_token])
        return first, last, list(seq.output), np.swapaxes(chosen, 0, 1)

    def _state_check(self, reference, params, ids, positions):
        """``state_step_error`` of the checked prompts (module docstring)."""
        new = positions.shape[1]
        probes = [self._probe(ids[b, :positions[b, 0] + 1].tolist(), new)
                  for b in range(ids.shape[0])]
        mine = np.zeros_like(ids)
        for b, (_, _, out, _) in enumerate(probes):
            seq = ids[b, :positions[b, 0] + 1].tolist() + out[:new - 1]
            mine[b, :len(seq)] = seq
        lengths = positions[:, -1] + 1
        _, _, states = reference.logits_at(
            params, mine, positions, lengths,
            [chosen[:, :n] for (*_, chosen), n in zip(probes, lengths)],
            {"routing_tie_eps": 1e9}, marks=positions[:, [0, -1]])

        def norm(a):                 # a head's Frobenius norm
            return np.sqrt(np.sum(np.square(a, dtype=np.float64),
                                  axis=(-2, -1)))

        step, final, kept, size = [], [], [], []
        for (first, last, _, _), theirs in zip(probes, states):
            for s0, s1, (r0, r1, k) in zip(first, last, theirs):
                step.append(norm((s1 - s0) - (r1 - r0)) / norm(r1 - r0))
                final.append(norm(s1 - r1) / norm(r1))
                kept.append(k)
                size.append(norm(r1) / norm(r1 - r0))
        step, final, kept, size = map(np.asarray, (step, final, kept, size))
        self.state_readings = {"step": step, "final": final, "kept": kept,
                               "size": size}
        slow = [np.median(e[k >= SLOW]) for e, k in zip(step, kept)
                if (k >= SLOW).any()]
        return {"state_step_error": float(max(slow)) if slow else np.inf,
                "slow_heads": int((kept >= SLOW).sum()),
                "heads": int(kept.size),
                "state_final_error": float(np.median(final))}


def build(config: Dict[str, Any], seed: int) -> NemotronHSystem:
    return NemotronHSystem(config, seed)
