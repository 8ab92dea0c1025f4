"""Builder of the GLM-5 configurations: the one place where the benchmark
touches ``paddle_tpu``'s GLM-5.  ``configs/<name>.json`` names it as
``"entry": "glm5:build"``; the serving job sees only the methods below
(``jobs/serve.py`` is unchanged).

The configuration file holds the catalog row's keys as published; the
``deployment`` keys say which share of each layer this chip holds:
``n_routed_experts`` counts the experts HELD (the router keeps
``n_routed_experts x ep_degree``), ``vocab_size`` the rows of the slice.

``reference_logits_fn`` is where the two near-tie hazards are settled
(ISSUE 32, as ISSUE 28 settled routing): under ``capture_logits`` the
engine hands out the experts it chose for every token of the checked
sequences (``per_token``) and the positions it selected at the compared
positions (``per_logit``: a prompt's last, each decoded one); the reference
checks each against its own float32 scores and computes under them.  The
four limits stand in the configuration file, because the harness hands a
builder the configuration and the traffic file's ``engine`` block only.
"""
from __future__ import annotations

import json
from typing import Any, Dict

import numpy as np

LIMITS = ("routing_tie_eps", "routing_differ_share", "index_tie_eps",
          "index_differ_share")


class Glm5System:
    def __init__(self, config: Dict[str, Any], seed: int):
        self.config = config
        self.seed = int(seed)
        self.model = self.engine = None
        self.check_report: Dict[str, Any] = {}

    # -- sizes, for the benchmark's arithmetic ------------------------------
    @property
    def shape(self) -> Dict[str, int]:
        c = self.config
        return {"layers": c["num_hidden_layers"], "hidden": c["hidden_size"],
                "heads": c["num_attention_heads"], "vocab": c["vocab_size"],
                "kv_lora_rank": c["kv_lora_rank"],
                "qk_rope_head_dim": c["qk_rope_head_dim"],
                "index_heads": c["index_n_heads"],
                "index_dim": c["index_head_dim"],
                "index_topk": c["index_topk"],
                "expert_layers": (c["num_hidden_layers"]
                                  - c["first_k_dense_replace"]),
                "experts_held": c["n_routed_experts"],
                "expert_width": c["moe_intermediate_size"],
                "positions": c["max_position_embeddings"]}

    # -- the model -------------------------------------------------------------
    def _model_config(self):
        from paddle_tpu.models.glm5 import Glm5Config
        c = self.config
        if (c["topk_method"] != "noaux_tc" or c["scoring_func"] != "sigmoid"
                or c["n_group"] != 1 or c["topk_group"] != 1
                or c["rope_parameters"]["rope_type"] != "default"
                or not c["rope_interleave"] or c["hidden_act"] != "silu"
                or c["attention_bias"] or c["tie_word_embeddings"]
                or c["moe_layer_freq"] != 1
                or c["num_nextn_predict_layers"] != 0
                or c["qk_head_dim"] != (c["qk_nope_head_dim"]
                                        + c["qk_rope_head_dim"])):
            raise ValueError("a GLM-5 this builder cannot build")
        return Glm5Config(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            moe_intermediate_size=c["moe_intermediate_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], index_n_heads=c["index_n_heads"],
            index_head_dim=c["index_head_dim"], index_topk=c["index_topk"],
            n_routed_experts=c["n_routed_experts"] * c["ep_degree"],
            n_shared_experts=c["n_shared_experts"],
            num_experts_per_tok=c["num_experts_per_tok"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            scoring_func=c["scoring_func"],
            routed_scaling_factor=c["routed_scaling_factor"],
            norm_topk_prob=c["norm_topk_prob"],
            first_k_dense_replace=c["first_k_dense_replace"],
            rms_norm_eps=c["rms_norm_eps"],
            rope_theta=c["rope_parameters"]["rope_theta"],
            max_position_embeddings=c["max_position_embeddings"],
            initializer_range=c["initializer_range"],
            dtype=c["serve_weights_dtype"], ep_degree=c["ep_degree"],
            ep_rank=c["ep_rank"])

    def build_for_serving(self, engine_args: Dict[str, Any]):
        """``ServingEngine`` over the model, every tensor made in the type
        it is served in (a float32 copy of this model does not fit)."""
        import paddle_tpu as pt
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.models.glm5 import Glm5ForCausalLM
        from paddle_tpu.observability.compilecache import (
            enable_persistent_cache)
        from paddle_tpu.observability.registry import MetricsRegistry
        enable_persistent_cache()
        pt.seed(self.seed % (2 ** 31 - 1))
        self.model = Glm5ForCausalLM(self._model_config())
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
            a = p + "attn."
            layer = {"input_norm": g(p + "input_norm.weight"),
                     "q_a": g(a + "q_a"),
                     "q_a_norm": g(a + "q_a_norm.weight"),
                     "q_b": g(a + "q_b"), "kv_a": g(a + "kv_a"),
                     "kv_a_norm": g(a + "kv_a_norm.weight"),
                     "kv_b": g(a + "kv_b"), "o": g(a + "o"),
                     "index_q_b": g(a + "index_q_b"),
                     "index_k": g(a + "index_k"),
                     "index_k_norm_w": g(a + "index_k_norm.weight"),
                     "index_k_norm_b": g(a + "index_k_norm.bias"),
                     "index_w": g(a + "index_w"),
                     "post_attn_norm": g(p + "post_attn_norm.weight")}
            m = p + "mlp."
            if m + "router" in params:
                layer.update(router=g(m + "router"),
                             router_bias=g(m + "router_bias"),
                             w_gate=g(m + "w_gate"), w_up=g(m + "w_up"),
                             w_down=g(m + "w_down"))
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

    def _captured(self, ids, lengths, prompt_lens):
        """What the engine handed out for the checked sequences, found by
        their prompts among the finished requests that captured: the
        experts of every cached token ``(expert layers, n, k)`` and the
        positions selected at the compared ones ``(layers, K,
        index_topk)``."""
        captured = [s for s in self.engine.sched.finished.values()
                    if s.capture_logits and s.per_token and s.per_logit]
        routing, selections = [], []
        for row, n, p in zip(np.asarray(ids), lengths, prompt_lens):
            match = [s for s in captured
                     if list(s.prompt) == row[:p].tolist()]
            if not match:
                raise LookupError("no captured choices for a checked "
                                  "sequence: the engine handed none out")
            seq = match[-1]
            chosen = np.concatenate([c["moe_topk"] for c in seq.per_token])
            routing.append(np.swapaxes(chosen, 0, 1)[:, :n])
            sel = np.stack([c["dsa_selected"] for c in seq.per_logit])
            selections.append(np.swapaxes(sel, 0, 1))     # (layers, K, topk)
        return routing, selections

    def reference_logits_fn(self):
        """``fn(params, ids, positions) -> logits``: the reference's
        forward pass under the engine's checked choices."""
        from perfbench.reference import glm5 as ref
        reference = ref.Reference(self._reference_cfg())
        limits = {k: float(self.config[k]) for k in LIMITS}

        def fn(params, ids, positions):
            positions = np.asarray(positions)
            lengths = positions[:, -1] + 1
            routing, selections = self._captured(ids, lengths,
                                                 positions[:, 0] + 1)
            selections = [s[:, :positions.shape[1]] for s in selections]
            logits, report = reference.logits_at(
                params, ids, positions, lengths, routing, selections, limits)
            self.check_report = dict(report, limits=limits)
            print("choices_check: " + json.dumps(self.check_report),
                  flush=True)
            return logits

        return fn


def build(config: Dict[str, Any], seed: int) -> Glm5System:
    return Glm5System(config, seed)
