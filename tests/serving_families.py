"""The model families the serving tests drive a ``ServingEngine`` over: a
GPT (two page arrays of ``(heads, head_dim)`` a layer) and a DeepSeek-V2
(one latent row a layer, one dense and one expert layer, rank 0 of 4: the
rank the benchmark's cell runs).  A test that asks for the ``family``
fixture runs once for each; ``tiny_model()`` then builds that family's."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                           deepseek_v2_tiny)

# logits a row of ``tiny_model()``, by family
VOCAB = {"deepseek_v2": 96, "gpt": 32}
_current = "gpt"


@pytest.fixture(params=sorted(VOCAB))
def family(request, monkeypatch):
    monkeypatch.setattr(sys.modules[__name__], "_current", request.param)
    return request.param


def tiny_model(max_pos=32):
    """Two layers, ``max_pos`` positions, in eval mode, from seed 7."""
    pt.seed(7)
    if _current == "gpt":
        m = GPTForCausalLM(GPTConfig(
            vocab_size=32, hidden_size=32, num_layers=2, num_heads=2,
            ffn_hidden_size=64, max_position_embeddings=max_pos,
            hidden_dropout=0.0, attention_dropout=0.0))
    else:
        m = DeepseekV2ForCausalLM(deepseek_v2_tiny(
            ep_degree=4, ep_rank=0, num_layers=2, initializer_range=0.2,
            max_position_embeddings=max_pos))
    m.eval()
    return m


def dense_forward(model):
    """``ids -> (tokens, vocab)`` logits of one sequence, no cache: one
    program a width."""
    apply, params = jax.jit(model.apply), model.state_dict()
    return lambda ids: np.asarray(
        apply(params, jnp.asarray([ids], jnp.int32)))[0]


def dense_continuation(model, prompt, max_new):
    """Greedy tokens without a cache: the model's own ``generate``, or for
    a family without one its plain forward over the sequence (at one
    width: under a causal mask what follows a position does not reach
    it)."""
    if hasattr(model, "generate"):
        out = model.generate(jnp.asarray([prompt], jnp.int32),
                             max_new_tokens=max_new, temperature=0.0)
        return np.asarray(out)[0, len(prompt):].tolist()
    forward = dense_forward(model)
    seq, width = list(prompt), len(prompt) + max_new
    for _ in range(max_new):
        logits = forward(seq + [0] * (width - len(seq)))
        seq.append(int(logits[len(seq) - 1].argmax()))
    return seq[len(prompt):]
