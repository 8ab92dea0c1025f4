"""Builder of the GPT-3 configurations: the one place where the benchmark
touches ``paddle_tpu``'s GPT.  ``configs/<name>.json`` names it as
``"entry": "gpt:build"``; the jobs see only the methods below, so another
architecture arrives as another builder file and its configurations.

The three set-up recipes are those of ``chip_smoke.py`` (the only ones known
to run on the chip, PR 21), copied, not imported: model + AdamW + amp O1 in
one donated ``jax.jit`` step; ``ServingEngine``'s arguments; dp x mp + ZeRO-1
+ recompute through ``fleet``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


class GPTSystem:
    def __init__(self, config: Dict[str, Any], seed: int):
        self.config = config
        self.seed = int(seed)
        self.parallel: Optional[Dict[str, Any]] = config.get("parallel")
        self.mesh = None
        self.model = None
        self.cfg = None

    # -- sizes, for the benchmark's arithmetic ------------------------------
    @property
    def shape(self) -> Dict[str, int]:
        c = self.config
        return {"layers": c["num_hidden_layers"], "hidden": c["hidden_size"],
                "heads": c["num_attention_heads"], "head_dim": c["head_dim"],
                "ffn": c["intermediate_size"], "vocab": c["vocab_size"],
                "positions": c["max_position_embeddings"]}

    def n_params(self) -> int:
        return int(sum(v.size for v in self.model.state_dict().values()))

    # -- the model -----------------------------------------------------------
    def _gpt_config(self, **kw):
        from paddle_tpu.models.gpt import GPTConfig
        c = self.config
        if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
            raise ValueError("hidden_size != num_attention_heads * head_dim")
        return GPTConfig(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            ffn_hidden_size=c["intermediate_size"],
            max_position_embeddings=c["max_position_embeddings"],
            layer_norm_epsilon=c["layer_norm_epsilon"],
            hidden_dropout=0.0, attention_dropout=0.0,
            dtype=c["dtype"], use_pallas_attention=True, **kw)

    def _seed_program(self) -> None:
        import paddle_tpu as pt
        # any whole number up to a little over 2**31: keep it in 31 bits
        pt.seed(self.seed % (2 ** 31 - 1))

    def build_for_training(self):
        """Model in train mode, optimizer, state and the donated jit step
        (not yet compiled).  Under ``parallel`` the fleet mesh is built
        first and the model and optimizer pass through ``fleet``."""
        import jax
        import paddle_tpu as pt
        from paddle_tpu import amp
        from paddle_tpu.framework import random as fw_random
        from paddle_tpu.models import GPTForCausalLM
        from paddle_tpu.observability.compilecache import (
            enable_persistent_cache)
        enable_persistent_cache()
        tr = self.config["training"]
        par = self.parallel
        if par:
            from paddle_tpu.distributed import fleet
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": par["dp_degree"],
                                       "mp_degree": par["mp_degree"],
                                       "pp_degree": par["pp_degree"]}
            if par.get("zero_stage"):
                strategy.sharding = True
                strategy.sharding_configs = {"stage": par["zero_stage"],
                                             "shard_weight_update": True}
            strategy.recompute = bool(par.get("recompute"))
            fleet.init(is_collective=True, strategy=strategy)
            self.mesh = fleet.get_mesh()
        self.cfg = self._gpt_config()
        self._seed_program()
        model = GPTForCausalLM(self.cfg)
        model.train()
        opt = pt.optimizer.AdamW(learning_rate=tr["learning_rate"],
                                 weight_decay=tr["weight_decay"])
        if par:
            model = fleet.distributed_model(model)
            opt = fleet.distributed_optimizer(opt)
        self.model, self.optimizer = model, opt
        params = model.state_dict()
        opt_state = opt.init(params)
        level = tr["amp"]

        def train_step(params, opt_state, ids, key):
            def loss_fn(p):
                with fw_random.key_scope(key):
                    with amp.auto_cast(level=level, dtype="bfloat16"):
                        # labels = inputs: the model shifts by one itself
                        loss, _ = model.apply(p, ids, labels=ids)
                return loss
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state = opt.apply_gradients(grads, params, opt_state)
            return loss, params, opt_state

        return params, opt_state, jax.jit(train_step, donate_argnums=(0, 1))

    def put_batch(self, ids):
        """Host batch -> device, laid out as the step takes it."""
        import jax.numpy as jnp
        if self.mesh is not None:
            import paddle_tpu.distributed as dist
            return dist.shard_batch(jnp.asarray(ids))
        return jnp.asarray(ids)

    def build_for_serving(self, engine_args: Dict[str, Any]):
        """``ServingEngine`` over the model in the type it is served in."""
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.models import GPTForCausalLM
        from paddle_tpu.observability.compilecache import (
            enable_persistent_cache)
        from paddle_tpu.observability.registry import MetricsRegistry
        # before the model is built: the engine turns the cache on too, but
        # by then the initialisers' programs have compiled under jax's
        # default floors and are never stored (21 misses a run, PR 24)
        enable_persistent_cache()
        self.cfg = self._gpt_config()
        self._seed_program()
        model = GPTForCausalLM(self.cfg)
        if self.config.get("serve_weights_dtype", "float32") != "float32":
            model.astype(self.config["serve_weights_dtype"])
        self.model = model
        self.registry = MetricsRegistry()
        return ServingEngine(
            model, max_seqs=engine_args["max_seqs"],
            max_model_len=engine_args["max_model_len"],
            kv_block_size=engine_args.get("kv_block_size"),
            num_kv_blocks=engine_args["num_kv_blocks"],
            capture_logits=False, registry=self.registry)

    # -- the plain reference's view of the program's parameters -------------
    def reference_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        g = lambda k: params[k]          # noqa: E731
        layers = []
        for i in range(self.config["num_hidden_layers"]):
            p = f"gpt.h.{i}."
            layers.append({
                "ln1_g": g(p + "ln_1.weight"), "ln1_b": g(p + "ln_1.bias"),
                "w_qkv": g(p + "attn.qkv_proj.weight"),
                "b_qkv": g(p + "attn.qkv_proj.bias"),
                "w_o": g(p + "attn.out_proj.weight"),
                "b_o": g(p + "attn.out_proj.bias"),
                "ln2_g": g(p + "ln_2.weight"), "ln2_b": g(p + "ln_2.bias"),
                "w_in": g(p + "mlp.fc_in.weight"),
                "b_in": g(p + "mlp.fc_in.bias"),
                "w_out": g(p + "mlp.fc_out.weight"),
                "b_out": g(p + "mlp.fc_out.bias")})
        return {"wte": g("gpt.wte.weight"), "wpe": g("gpt.wpe"),
                "layers": layers, "lnf_g": g("gpt.ln_f.weight"),
                "lnf_b": g("gpt.ln_f.bias")}

    def reference_loss_fn(self):
        import jax
        from perfbench.reference import gpt as ref
        heads, eps = (self.config["num_attention_heads"],
                      self.config["layer_norm_epsilon"])
        return jax.jit(lambda p, ids: ref.lm_loss(p, ids, heads, eps))

    def reference_logits_fn(self):
        import jax
        from perfbench.reference import gpt as ref
        heads, eps = (self.config["num_attention_heads"],
                      self.config["layer_norm_epsilon"])
        return jax.jit(
            lambda p, ids, pos: ref.logits_at(p, ids, pos, heads, eps))


def build(config: Dict[str, Any], seed: int) -> GPTSystem:
    return GPTSystem(config, seed)
