"""ResNet + BERT model tests (BASELINE configs #2/#3).

The reference's model-zoo tests (python/paddle/tests/test_vision_models.py
doctrine) check construction + forward shapes; here we add the golden-loss
training check and, for BERT, the TP parallel == serial invariant."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.framework import random as fw_random


@pytest.fixture(autouse=True)
def _clean():
    yield
    dist.set_hybrid_communicate_group(None)


class TestResNet:
    def test_forward_shapes_all_depths(self):
        from paddle_tpu.vision.models import (resnet18, resnet50,
                                              wide_resnet50_2)
        pt.seed(0)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 3, 64, 64),
                        jnp.float32)
        for ctor, feat in ((resnet18, 512), (resnet50, 2048)):
            m = ctor(num_classes=10)
            m.eval()
            out = m(x)
            assert out.shape == (2, 10), (ctor.__name__, out.shape)
        m = wide_resnet50_2(num_classes=0, with_pool=True)
        m.eval()
        assert m(x).shape == (2, 2048, 1, 1)

    def test_resnet18_trains_on_toy_batch(self):
        from paddle_tpu.vision.models import resnet18
        pt.seed(1)
        model = resnet18(num_classes=4)
        model.train()
        params = model.state_dict()
        opt = pt.optimizer.Adam(learning_rate=1e-3)
        state = opt.init(params)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(8, 3, 32, 32), jnp.float32)
        y = jnp.asarray(rng.randint(0, 4, (8,)), jnp.int32)

        buf_names = {name for name, _ in model.named_buffers()}

        def step(p, s):
            def loss_fn(q):
                out, newvars = model.apply(q, x, mutable=True)
                loss = jnp.mean(pt.nn.functional.cross_entropy(out, y))
                return loss, newvars
            (loss, newvars), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p)
            p2, s2 = opt.apply_gradients(grads, p, s)
            # fold updated BN running stats back into the train state
            # (type-preserving: the optimizer state treedef is OrderedDict)
            for k in buf_names:
                p2[k] = newvars[k]
            return loss, p2, s2

        jitted = jax.jit(step)
        losses = []
        for _ in range(6):
            loss, params, state = jitted(params, state)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0], losses

    def test_batchnorm_running_stats_update(self):
        from paddle_tpu.vision.models import resnet18
        pt.seed(2)
        model = resnet18(num_classes=4)
        model.train()
        params = model.state_dict()
        x = jnp.asarray(np.random.RandomState(1).randn(4, 3, 32, 32) * 3 + 1,
                        jnp.float32)
        _, newvars = model.apply(params, x, mutable=True)
        k = "bn1._mean"
        assert k in newvars
        assert not np.allclose(np.asarray(newvars[k]),
                               np.asarray(params[k]))


class TestBert:
    def _data(self, cfg, B=4, S=32, seed=0):
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
        types = (rng.rand(B, S) > 0.5).astype(np.int32)
        mask = np.ones((B, S), np.int32)
        mask[:, S - 4:] = 0                      # padded tail
        mlm = np.where(rng.rand(B, S) < 0.15, ids, -100).astype(np.int32)
        nsp = rng.randint(0, 2, (B,)).astype(np.int32)
        return (jnp.asarray(ids), jnp.asarray(types), jnp.asarray(mask),
                jnp.asarray(mlm), jnp.asarray(nsp))

    def test_pretraining_forward_and_loss(self):
        from paddle_tpu.models import BertForPretraining, bert_tiny
        pt.seed(3)
        cfg = bert_tiny()
        model = BertForPretraining(cfg)
        model.eval()
        params = model.state_dict()
        ids, types, mask, mlm, nsp = self._data(cfg)
        logits, nsp_logits = model.apply(params, ids, types, mask)
        assert logits.shape == (4, 32, cfg.vocab_size)
        assert nsp_logits.shape == (4, 2)
        loss, _ = model.apply(params, ids, types, mask, mlm_labels=mlm,
                              nsp_labels=nsp)
        assert np.isfinite(float(loss))

    def test_pretraining_loss_decreases(self):
        from paddle_tpu.models import BertForPretraining, bert_tiny
        pt.seed(4)
        cfg = bert_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        model = BertForPretraining(cfg)
        model.train()
        params = model.state_dict()
        opt = pt.optimizer.AdamW(learning_rate=1e-3)
        state = opt.init(params)
        ids, types, mask, mlm, nsp = self._data(cfg)

        def step(p, s, key):
            def loss_fn(q):
                with fw_random.key_scope(key):
                    loss, _ = model.apply(q, ids, types, mask,
                                          mlm_labels=mlm, nsp_labels=nsp)
                return loss
            loss, grads = jax.value_and_grad(loss_fn)(p)
            p2, s2 = opt.apply_gradients(grads, p, s)
            return loss, p2, s2

        jitted = jax.jit(step)
        losses = []
        for i in range(5):
            loss, params, state = jitted(
                params, state, jax.random.fold_in(jax.random.key(0), i))
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0], losses

    @pytest.mark.skipif(jax.device_count() < 8,
                        reason="needs the 8-device CPU mesh")
    def test_tp_parallel_matches_serial(self):
        from paddle_tpu.models import BertForPretraining, bert_tiny
        pt.seed(5)
        cfg = bert_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        model = BertForPretraining(cfg)
        model.eval()
        params = model.state_dict()
        ids, types, mask, mlm, nsp = self._data(cfg)
        loss_s, _ = model.apply(params, ids, types, mask, mlm_labels=mlm,
                                nsp_labels=nsp)

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        fleet.distributed_model(model)
        params_d = model.state_dict()
        assert params_d[
            "bert.embeddings.word_embeddings.weight"
        ].sharding.spec == P("mp", None)
        loss_p, _ = jax.jit(
            lambda v: model.apply(v, dist.shard_batch(ids),
                                  dist.shard_batch(types),
                                  dist.shard_batch(mask),
                                  mlm_labels=dist.shard_batch(mlm),
                                  nsp_labels=dist.shard_batch(nsp))
        )(params_d)
        np.testing.assert_allclose(float(loss_p), float(loss_s), rtol=2e-5)

    def test_sequence_classification(self):
        from paddle_tpu.models import (BertForSequenceClassification,
                                       bert_tiny)
        pt.seed(6)
        cfg = bert_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        model = BertForSequenceClassification(cfg, num_classes=3)
        model.eval()
        params = model.state_dict()
        ids, types, mask, _, _ = self._data(cfg)
        labels = jnp.asarray([0, 1, 2, 1], jnp.int32)
        loss, logits = model.apply(params, ids, types, mask, labels=labels)
        assert logits.shape == (4, 3)
        assert np.isfinite(float(loss))


class TestGPTGenerate:
    def test_pallas_decode_kernel_matches_xla_cache_path(self):
        """Single-token decode through flash_attention_kvcache must produce
        the same greedy continuation as the masked XLA cache path."""
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        outs = {}
        for pallas in (False, True):
            pt.seed(11)   # identical weights across the two paths
            cfg = GPTConfig(hidden_size=64, num_layers=2, num_heads=4,
                            max_position_embeddings=64, vocab_size=256,
                            hidden_dropout=0.0, attention_dropout=0.0,
                            use_pallas_attention=pallas)
            model = GPTForCausalLM(cfg)
            model.eval()
            prompt = jnp.asarray(
                np.random.RandomState(0).randint(0, 256, (2, 8)), jnp.int32)
            outs[pallas] = np.asarray(
                model.generate(prompt, max_new_tokens=8, temperature=0.0))
        np.testing.assert_array_equal(outs[False], outs[True])

    def test_greedy_matches_full_recompute(self):
        """Incremental static-cache decode == rerunning the full forward at
        every step (the CacheKV correctness invariant)."""
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        pt.seed(9)
        cfg = GPTConfig(hidden_size=64, num_layers=2, num_heads=4,
                        max_position_embeddings=64, vocab_size=256,
                        hidden_dropout=0.0, attention_dropout=0.0)
        model = GPTForCausalLM(cfg)
        model.eval()
        rng = np.random.RandomState(0)
        prompt = jnp.asarray(rng.randint(0, 256, (2, 8)), jnp.int32)

        out = model.generate(prompt, max_new_tokens=8, temperature=0.0)
        assert out.shape == (2, 16)

        # naive: full forward each step, argmax last logit
        ids = prompt
        for _ in range(8):
            logits = model(ids)
            nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
            ids = jnp.concatenate([ids, nxt[:, None].astype(jnp.int32)],
                                  axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ids))

    def test_eos_early_stop_and_sampling(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        pt.seed(10)
        cfg = GPTConfig(hidden_size=32, num_layers=1, num_heads=2,
                        max_position_embeddings=64, vocab_size=64,
                        hidden_dropout=0.0, attention_dropout=0.0)
        model = GPTForCausalLM(cfg)
        model.eval()
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        out = model.generate(prompt, max_new_tokens=20, temperature=1.0,
                             top_k=8, key=jax.random.key(0))
        assert out.shape[1] <= 23
        # deterministic per key
        out2 = model.generate(prompt, max_new_tokens=20, temperature=1.0,
                              top_k=8, key=jax.random.key(0))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


class TestGPTScopes:
    """``jax.named_scope`` marks the owner of device operations in the
    trace and in the lowered HLO (ISSUE 26): metadata only."""

    @staticmethod
    def lowered_names(hybrid: bool):
        import re
        from paddle_tpu import amp
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        if hybrid:
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                       "pp_degree": 1}
            strategy.sharding = True
            strategy.sharding_configs = {"stage": 1,
                                         "shard_weight_update": True}
            strategy.recompute = True
            fleet.init(is_collective=True, strategy=strategy)
        pt.seed(3)
        cfg = GPTConfig(hidden_size=32, num_layers=2, num_heads=2,
                        ffn_hidden_size=64, max_position_embeddings=64,
                        vocab_size=128, hidden_dropout=0.0,
                        attention_dropout=0.0)
        model = GPTForCausalLM(cfg)
        model.train()
        opt = pt.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01)
        if hybrid:
            model = fleet.distributed_model(model)
            opt = fleet.distributed_optimizer(opt)
        params = model.state_dict()
        state = opt.init(params)

        def train_step(params, state, ids, key):
            def loss_fn(p):
                with fw_random.key_scope(key):
                    with amp.auto_cast(level="O1", dtype="bfloat16"):
                        loss, _ = model.apply(p, ids, labels=ids)
                return loss
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, state = opt.apply_gradients(grads, params, state)
            return loss, params, state

        ids = jnp.zeros((4, 32), jnp.int32)
        if hybrid:
            ids = dist.shard_batch(ids)
        text = jax.jit(train_step).lower(
            params, state, ids, jax.random.PRNGKey(0)).as_text(
                debug_info=True)
        return set(re.findall(r'loc\("([^"]+)"', text))

    @staticmethod
    def has(names, *parts):
        return any(all(p in n for p in parts) for n in names)

    def test_train_step_carries_the_scopes(self):
        names = self.lowered_names(hybrid=False)
        for scope in ("gpt.embed", "gpt.block", "gpt.ln_f",
                      "gpt.head_loss", "adamw.update"):
            assert self.has(names, "jit(train_step)/", scope), scope
        for inner in ("ln", "attn", "mlp"):
            assert self.has(names, f"jvp(gpt.block)/{inner}/"), inner
        # backward names itself from the forward's scope
        assert self.has(names, "transpose(jvp(gpt.block))/", "/attn/")
        assert self.has(names, "transpose(jvp(gpt.head_loss))")
        # the optimizer is no part of any model scope
        assert not self.has(names, "gpt.", "adamw.update")

    def test_hybrid_step_carries_the_parallel_scopes(self):
        names = self.lowered_names(hybrid=True)
        for scope in ("zero.pack", "zero.update", "zero.unpack"):
            assert self.has(names, f"jit(train_step)/{scope}/"), scope
        # a layer's scope sits inside the block part that calls it, through
        # recompute; the contraction GSPMD completes with an all-reduce is
        # the row-parallel one
        assert self.has(names, "jvp(gpt.block)", "/attn/mp.column_parallel/")
        assert self.has(names, "jvp(gpt.block)", "/mlp/mp.row_parallel/",
                        "dot_general")
        assert self.has(names, "checkpoint", "mp.row_parallel")

    def test_serving_step_carries_the_scopes(self):
        import re
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.engine import pack_step_inputs
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.observability.registry import MetricsRegistry
        pt.seed(3)
        cfg = GPTConfig(hidden_size=32, num_layers=1, num_heads=2,
                        ffn_hidden_size=64, max_position_embeddings=32,
                        vocab_size=64, hidden_dropout=0.0,
                        attention_dropout=0.0)
        eng = ServingEngine(GPTForCausalLM(cfg), max_seqs=2,
                            kv_block_size=4, registry=MetricsRegistry())
        tables = np.zeros((2, 8), np.int32)
        lens = np.ones((2,), np.int32)
        slots = np.zeros((2, 1), np.int32)
        packed = pack_step_inputs(np.zeros((2, 1)), np.zeros((2,)), 0,
                                  tables, lens, slots)
        text = eng._build_step_fn().lower(
            eng._params, packed, eng.cache.pages, jax.random.PRNGKey(0),
            eng._no_prev, rows=2, chunk=1).as_text(debug_info=True)
        names = set(re.findall(r'loc\("([^"]+)"', text))
        for scope in ("gpt.embed", "gpt.block/attn/", "gpt.block/mlp/",
                      "gpt.head/"):
            assert self.has(names, scope), scope
