"""Builder of the MiMo-V2-Flash configurations: the one place where the
benchmark touches ``paddle_tpu``'s MiMo-V2.  ``configs/<name>.json`` names
it as ``"entry": "mimo_v2:build"``; the serving job sees only the methods
below (``jobs/serve.py`` is unchanged).

The configuration file holds the catalog row's keys as published; the
``deployment`` keys say which share of each layer this chip holds:
``n_routed_experts`` counts the experts HELD (the router keeps
``n_routed_experts x ep_degree``), ``vocab_size`` the rows of the slice.

The traffic file's ``engine`` block gives ``num_kv_blocks`` a layer KIND
(``{"full": .., "window": ..}``): the engine keeps a page pool and a block
table for each (``paddle_tpu/inference/kv_cache.py``).

``reference_logits_fn`` settles the router's near-ties as DeepSeek-V2's and
GLM-5's builders do: under ``capture_logits`` the engine hands out the
experts it chose for every token of the checked sequences (``per_token``);
the reference checks each against its own float32 scores and computes under
them.  The two limits stand in the configuration file, because the harness
hands a builder the configuration and the traffic file's ``engine`` block
only.
"""
from __future__ import annotations

import json
from typing import Any, Dict

import numpy as np

LIMITS = ("routing_tie_eps", "routing_differ_share")


class MimoV2System:
    def __init__(self, config: Dict[str, Any], seed: int):
        self.config = config
        self.seed = int(seed)
        self.model = self.engine = None
        self.check_report: Dict[str, Any] = {}

    # -- sizes, for the benchmark's arithmetic ------------------------------
    @property
    def shape(self) -> Dict[str, int]:
        c = self.config
        windows = sum(c["hybrid_layer_pattern"])
        return {"layers": c["num_hidden_layers"], "hidden": c["hidden_size"],
                "heads": c["num_attention_heads"], "vocab": c["vocab_size"],
                "qk_head_dim": c["head_dim"], "v_head_dim": c["v_head_dim"],
                "full_layers": c["num_hidden_layers"] - windows,
                "window_layers": windows,
                "full_kv_heads": c["num_key_value_heads"],
                "window_kv_heads": c["swa_num_key_value_heads"],
                "window": c["sliding_window"],
                "expert_layers": sum(c["moe_layer_freq"]),
                "experts_held": c["n_routed_experts"],
                "expert_width": c["moe_intermediate_size"],
                "positions": c["max_position_embeddings"]}

    # -- the model -------------------------------------------------------------
    def _model_config(self):
        from paddle_tpu.models.mimo_v2 import MimoV2Config
        c = self.config
        layers = c["num_hidden_layers"]
        if (c["topk_method"] != "noaux_tc" or c["scoring_func"] != "sigmoid"
                or c["n_group"] != 1 or c["topk_group"] != 1
                or c["hidden_act"] != "silu" or c["attention_bias"]
                or c["tie_word_embeddings"] or c["n_shared_experts"]
                or c["routed_scaling_factor"] is not None
                or c["swa_num_attention_heads"] != c["num_attention_heads"]
                or c["swa_head_dim"] != c["head_dim"]
                or c["swa_v_head_dim"] != c["v_head_dim"]
                or c["sliding_window_size"] != c["sliding_window"]
                or len(c["hybrid_layer_pattern"]) != layers
                or len(c["moe_layer_freq"]) != layers):
            raise ValueError("a MiMo-V2 this builder cannot build")
        return MimoV2Config(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            moe_intermediate_size=c["moe_intermediate_size"],
            num_layers=layers, num_heads=c["num_attention_heads"],
            head_dim=c["head_dim"], v_head_dim=c["v_head_dim"],
            num_kv_heads=c["num_key_value_heads"],
            swa_num_kv_heads=c["swa_num_key_value_heads"],
            sliding_window=c["sliding_window"],
            hybrid_layer_pattern=c["hybrid_layer_pattern"],
            moe_layer_freq=c["moe_layer_freq"],
            partial_rotary_factor=c["partial_rotary_factor"],
            rope_theta=c["rope_theta"], swa_rope_theta=c["swa_rope_theta"],
            attention_value_scale=c["attention_value_scale"],
            add_swa_attention_sink_bias=c["add_swa_attention_sink_bias"],
            add_full_attention_sink_bias=c["add_full_attention_sink_bias"],
            n_routed_experts=c["n_routed_experts"] * c["ep_degree"],
            n_shared_experts=0,
            num_experts_per_tok=c["num_experts_per_tok"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            scoring_func=c["scoring_func"], routed_scaling_factor=1.0,
            norm_topk_prob=c["norm_topk_prob"],
            rms_norm_eps=c["layernorm_epsilon"],
            max_position_embeddings=c["max_position_embeddings"],
            initializer_range=c["initializer_range"],
            dtype=c["serve_weights_dtype"], ep_degree=c["ep_degree"],
            ep_rank=c["ep_rank"])

    def build_for_serving(self, engine_args: Dict[str, Any]):
        """``ServingEngine`` over the model, every tensor made in the type
        it is served in (a float32 copy of this model does not fit)."""
        import paddle_tpu as pt
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.models.mimo_v2 import MimoV2ForCausalLM
        from paddle_tpu.observability.compilecache import (
            enable_persistent_cache)
        from paddle_tpu.observability.registry import MetricsRegistry
        enable_persistent_cache()
        pt.seed(self.seed % (2 ** 31 - 1))
        self.model = MimoV2ForCausalLM(self._model_config())
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
                     "q": g(a + "q"), "k": g(a + "k"), "v": g(a + "v"),
                     "o": g(a + "o"),
                     "post_attn_norm": g(p + "post_attn_norm.weight")}
            if a + "sink" in params:
                layer["sink"] = g(a + "sink")
            m = p + "mlp."
            if m + "router" in params:
                layer.update(router=g(m + "router"),
                             router_bias=g(m + "router_bias"),
                             w_gate=g(m + "w_gate"), w_up=g(m + "w_up"),
                             w_down=g(m + "w_down"))
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
        from perfbench.reference import mimo_v2 as ref
        reference = ref.Reference(self._reference_cfg())
        limits = {k: float(self.config[k]) for k in LIMITS}

        def fn(params, ids, positions):
            positions = np.asarray(positions)
            lengths = positions[:, -1] + 1
            routing = self._captured(ids, lengths, positions[:, 0] + 1)
            logits, report = reference.logits_at(
                params, ids, positions, lengths, routing, limits)
            self.check_report = dict(report, limits=limits)
            print("choices_check: " + json.dumps(self.check_report),
                  flush=True)
            return logits

        return fn


def build(config: Dict[str, Any], seed: int) -> MimoV2System:
    return MimoV2System(config, seed)
