"""The plain reference against the program's model at a tiny size in
float32, the seeded weights' layout against the program's, and the
lower-precision control, which has to come out as not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run, weights as W
from benchmark.reference import decoder_lm

DECODER = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, rope_theta=1e6,
               rms_norm_eps=1e-6)

def _same_layout(ours, theirs):
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ours),
                                 jax.tree_util.tree_leaves_with_path(theirs)):
        assert a.shape == b.shape, jax.tree_util.keystr(path)


def _llama():
    from aiko_services_tpu.models.llama import LlamaConfig
    return LlamaConfig(vocab=256, dim=64, ffn_dim=128, num_layers=2,
                       num_heads=4, num_kv_heads=2, max_seq_len=128,
                       rope_theta=1e6)


def _served_by_llama(seed, prompt, count):
    from aiko_services_tpu.models.llama import llama_greedy_decode
    params = W.decoder_weights(W.key_for(seed), DECODER, jnp.float32)
    decode = jax.jit(lambda params, prompt: llama_greedy_decode(
        params, _llama(), prompt, max_tokens=count))
    return np.asarray(decode(params, jnp.asarray([prompt], jnp.int32)))[
        0].tolist()


def test_seeded_weights_have_the_programs_layouts():
    from aiko_services_tpu.models.llama import llama_init
    _same_layout(W.decoder_weights(W.key_for(1), DECODER, jnp.float32),
                 llama_init(jax.random.PRNGKey(0), _llama()))
    a = W.decoder_layer(W.key_for(2**31 + 5), 1, DECODER, jnp.float32)
    b = W.decoder_layer(W.key_for(2**31 + 5), 1, DECODER, jnp.float32)
    c = W.decoder_layer(W.key_for(5), 1, DECODER, jnp.float32)
    assert jnp.array_equal(a["gate"]["w"], b["gate"]["w"])
    assert not jnp.array_equal(a["gate"]["w"], c["gate"]["w"])


def test_decoder_reference_agrees_with_models_llama_and_sees_a_wrong_token():
    seed = 2**31 + 7
    prompt = np.random.default_rng(0).integers(1, 256, size=20).tolist()
    served = _served_by_llama(seed, prompt, 8)
    wrong = list(served)
    wrong[3] = (wrong[3] + 1) % 256
    samples = [{"prompt": prompt, "served": served},
               {"prompt": prompt, "served": wrong}]
    checked = decoder_lm.check(samples, DECODER, seed, jnp.float32)
    gaps = checked["numbers"]["served_token_gap_std"]
    assert gaps[0] == pytest.approx(0.0, abs=1e-4)
    assert gaps[1] > 0.5 and checked["positions"] == 16
    # other weights than the served ones are a wrong answer too
    other = decoder_lm.check(samples[:1], DECODER, seed + 1, jnp.float32)
    assert other["numbers"]["served_token_gap_std"][0] > 0.5


@pytest.mark.parametrize("config_name", ["mistral-7b-v0.3-d16"])
def test_the_float8_control_comes_out_as_not_correct(config_name):
    """The control at a size a test run can hold: the reference with its
    weights rounded to float8 puts first, somewhere in a few hundred
    positions, a token that lies further below the full-precision best
    than the configuration's limit allows.  (At the cell's own size the
    readings are in PERF.md.)"""
    config = run.load_json("benchmark", "configs", config_name + ".json")
    limits = config["correctness"]["limits"]
    rng = np.random.default_rng(3)
    sizes = DECODER | dict(vocab_size=2048, hidden_size=128, head_dim=32,
                           intermediate_size=256, num_hidden_layers=4)
    samples = [{"prompt": rng.integers(1, 2048, size=96).tolist(),
                "served": rng.integers(1, 2048, size=96).tolist()}
               for _ in range(2)]
    control = decoder_lm.check(samples, sizes, 9, jnp.bfloat16,
                           control=True)["control"]
    assert any(max(control[name]) > limit for name, limit in limits.items()), \
        control
