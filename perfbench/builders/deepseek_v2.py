"""Builder of the DeepSeek-V2 configurations: the one place where the
benchmark touches ``paddle_tpu``'s DeepSeek-V2.  ``configs/<name>.json``
names it as ``"entry": "deepseek_v2:build"``; the serving job sees only the
methods below (``jobs/serve.py`` is the GPT cells', unchanged).

The configuration file holds the catalog row's keys as published; the
``deployment`` keys say which share of each layer this chip holds:
``n_routed_experts`` counts the experts HELD (the router keeps
``n_routed_experts_published``), ``vocab_size`` the rows of the slice.

``reference_logits_fn`` is where the routing hazard is settled (ISSUE 28):
the engine hands out, under ``capture_logits``, the experts it chose for
the checked sequences; the reference checks each choice against its own
float32 scores (``routing_tie_eps``) and computes under them.  A choice
outside the epsilon fails the comparison, and so do more than
``routing_differ_share`` of the choices falling other than the reference's
own.  Both limits stand in the configuration file, because the harness
hands a builder the configuration and the traffic file's ``engine`` block
only (the ``check`` block is read by ``jobs/serve.py`` alone).
"""
from __future__ import annotations

import json
from typing import Any, Dict

import numpy as np


class DeepseekV2System:
    def __init__(self, config: Dict[str, Any], seed: int):
        self.config = config
        self.seed = int(seed)
        self.model = self.engine = None
        self.routing_report: Dict[str, Any] = {}

    # -- sizes, for the benchmark's arithmetic ------------------------------
    @property
    def shape(self) -> Dict[str, int]:
        c = self.config
        return {"layers": c["num_hidden_layers"], "hidden": c["hidden_size"],
                "heads": c["num_attention_heads"], "vocab": c["vocab_size"],
                "kv_lora_rank": c["kv_lora_rank"],
                "qk_rope_head_dim": c["qk_rope_head_dim"],
                "expert_layers": (c["num_hidden_layers"]
                                  - c["first_k_dense_replace"]),
                "experts_held": c["n_routed_experts"],
                "expert_width": c["moe_intermediate_size"],
                "positions": c["max_position_embeddings"]}

    # -- the model -------------------------------------------------------------
    def _model_config(self):
        from paddle_tpu.models.deepseek_v2 import DeepseekV2Config
        c, rs = self.config, self.config["rope_scaling"]
        if (c["topk_method"] != "group_limited_greedy"
                or c["scoring_func"] != "softmax" or rs["type"] != "yarn"
                or c["hidden_act"] != "silu" or c["attention_bias"]
                or c["tie_word_embeddings"] or c["moe_layer_freq"] != 1):
            raise ValueError("a DeepSeek-V2 this builder cannot build")
        return DeepseekV2Config(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            moe_intermediate_size=c["moe_intermediate_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
            n_routed_experts=c["n_routed_experts"] * c["ep_degree"],
            n_shared_experts=c["n_shared_experts"],
            num_experts_per_tok=c["num_experts_per_tok"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            routed_scaling_factor=c["routed_scaling_factor"],
            norm_topk_prob=c["norm_topk_prob"],
            first_k_dense_replace=c["first_k_dense_replace"],
            rms_norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
            rope_factor=rs["factor"],
            rope_original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
            rope_mscale=rs["mscale"],
            rope_mscale_all_dim=rs["mscale_all_dim"],
            max_position_embeddings=c["max_position_embeddings"],
            initializer_range=c["initializer_range"],
            dtype=c["serve_weights_dtype"], ep_degree=c["ep_degree"],
            ep_rank=c["ep_rank"])

    def build_for_serving(self, engine_args: Dict[str, Any]):
        """``ServingEngine`` over the model, every tensor made in the type
        it is served in (a float32 copy of this model does not fit)."""
        import paddle_tpu as pt
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM
        from paddle_tpu.observability.compilecache import (
            enable_persistent_cache)
        from paddle_tpu.observability.registry import MetricsRegistry
        enable_persistent_cache()
        pt.seed(self.seed % (2 ** 31 - 1))
        self.model = DeepseekV2ForCausalLM(self._model_config())
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
        for i in range(self.config["num_hidden_layers"]):
            p = f"layers.{i}."
            layer = {"input_norm": g(p + "input_norm.weight"),
                     "q_a": g(p + "attn.q_a"),
                     "q_a_norm": g(p + "attn.q_a_norm.weight"),
                     "q_b": g(p + "attn.q_b"), "kv_a": g(p + "attn.kv_a"),
                     "kv_a_norm": g(p + "attn.kv_a_norm.weight"),
                     "kv_b": g(p + "attn.kv_b"), "o": g(p + "attn.o"),
                     "post_attn_norm": g(p + "post_attn_norm.weight")}
            m = p + "mlp."
            if m + "router" in params:
                layer.update(router=g(m + "router"), w_gate=g(m + "w_gate"),
                             w_up=g(m + "w_up"), w_down=g(m + "w_down"))
                if m + "shared.w_gate" in params:
                    layer["shared"] = (g(m + "shared.w_gate"),
                                       g(m + "shared.w_up"),
                                       g(m + "shared.w_down"))
            else:
                layer["dense"] = (g(m + "w_gate"), g(m + "w_up"),
                                  g(m + "w_down"))
            layers.append(layer)
        return {"embed": g("embed"), "head": g("head"),
                "norm": g("norm.weight"), "layers": layers}

    def _reference_cfg(self) -> Dict[str, Any]:
        c = dict(self.config)
        c["n_routed_experts"] = (self.config["n_routed_experts"]
                                 * self.config["ep_degree"])
        return c

    def _captured_routing(self, ids, lengths, prompt_lens):
        """The engine's choices for the checked sequences, found by their
        prompts among the finished requests that captured them."""
        captured = [s for s in self.engine.sched.finished.values()
                    if s.capture_logits and s.per_token]
        out = []
        for row, n, p in zip(np.asarray(ids), lengths, prompt_lens):
            match = [s for s in captured
                     if list(s.prompt) == row[:p].tolist()]
            if not match:
                raise LookupError("no captured routing for a checked "
                                  "sequence: the engine handed none out")
            chosen = np.concatenate([c["moe_topk"]
                                     for c in match[-1].per_token])
            out.append(np.swapaxes(chosen, 0, 1)[:, :n])  # (layers, n, k)
        return out

    def reference_logits_fn(self):
        """``fn(params, ids, positions) -> logits``: the reference's
        forward pass under the engine's checked routing choices."""
        from perfbench.reference import deepseek_v2 as ref
        reference = ref.Reference(self._reference_cfg())
        eps = float(self.config["routing_tie_eps"])
        share = float(self.config["routing_differ_share"])

        def fn(params, ids, positions):
            positions = np.asarray(positions)
            lengths = positions[:, -1] + 1
            routing = self._captured_routing(ids, lengths,
                                             positions[:, 0] + 1)
            logits, report = reference.logits_at(
                params, ids, positions, lengths, routing, eps, share)
            self.routing_report = dict(report, tie_eps=eps,
                                       differ_share=share)
            print("routing_check: " + json.dumps(self.routing_report),
                  flush=True)
            return logits

        return fn


def build(config: Dict[str, Any], seed: int) -> DeepseekV2System:
    return DeepseekV2System(config, seed)
