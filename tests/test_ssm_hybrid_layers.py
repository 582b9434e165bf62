# The Mamba-2 hybrid decoder (ISSUE 45: state-space layers whose 6 heads of
# 8 here share ONE B and ONE C of 16 and whose state is a SLOT's, beside
# grouped-query attention layers, a K/V group of 4, whose one pool leaf keeps
# a head's V and K side by side; a layer pattern that is not periodic) at a
# small size on the CPU in float32, the layer's own functions, no decoder:
# the recurrence's three forms, the model against the benchmark's plain
# reference (benchmark/reference/ssm_hybrid_lm.py: the recurrence token by
# token, plain softmax, precision "highest"), that reference against the
# published implementation where `transformers` imports, a slot that decodes
# nothing.  This file holds the suite's SIZES and its `CASES`
# (tests/paged_model_cases.py); the cases that build or serve through a
# decoder are in test_0_served_ssm_hybrid.py.
#
# Comparisons are of LOGITS or states, never of sampled tokens.  Each
# tolerance states its reason.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_model_cases import PagedModelCases

from aiko_services_tpu.models import ssm_hybrid as M
from benchmark import weights_ssm_hybrid as W
from benchmark.reference import ssm_hybrid_lm as R

SEED = 2**31 + 45
MAMBA, ATTN = "mamba", "attention"
# the published keys at a size a test holds
SIZES = dict(
    hidden_size=64, vocab_size=256, shared_intermediate_size=128,
    intermediate_size=128, num_hidden_layers=8,
    layer_types=[MAMBA, ATTN, MAMBA, MAMBA, MAMBA, MAMBA, ATTN, MAMBA],
    num_attention_heads=8, num_key_value_heads=2, mamba_n_heads=6,
    mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4, mamba_n_groups=1,
    mamba_expand=0.75, mamba_conv_bias=True, mamba_proj_bias=False,
    mamba_chunk_size=256, hidden_act="silu", attention_bias=False,
    tie_word_embeddings=True, position_embedding_type="nope",
    num_local_experts=0, num_experts_per_tok=0,
    normalization_function="rmsnorm", rms_norm_eps=1e-5,
    embedding_multiplier=12, attention_multiplier=0.125,
    residual_multiplier=0.22, logits_scaling=8)
# float32 against float32 at "highest": what is left is the order of the
# sums (chunked against one-token recurrence, a walk against one softmax),
# a few float32 ulps of logits whose spread is 0.001 (a tied head over a
# narrow embedding): measured 1e-8 at most.  bfloat16 anywhere reads 3e-4.
LOGIT_TOLERANCE = 1e-7


CASES = PagedModelCases(
    "ssm_hybrid_decoder", W,
    lambda tokens, sizes, seed: R.forward_logits(tokens[None], sizes, seed,
                                                 jnp.float32)[0],
    SIZES, SEED)
model_config, reference_logits = CASES.model_config, CASES.reference_logits
TOKENS = np.random.default_rng(0).integers(1, 256, size=90)


def test_seeded_weights_have_the_programs_layout():
    assert model_config() == M.SSM_HYBRID_PRESETS["tiny"]
    CASES.has_the_layout_of(M.ssm_hybrid_init)


def test_full_forward_agrees_with_the_reference():
    """90 tokens through six Mamba layers (one chunk, padded) and two
    attention layers, the four multipliers, the tied head."""
    with jax.default_matmul_precision("highest"):
        gap, spread = CASES.forward_gap(M.ssm_hybrid_forward, TOKENS)
    assert spread > 0.0008
    assert gap < LOGIT_TOLERANCE


def test_bfloat16_would_fail():
    gap, _ = CASES.forward_gap(M.ssm_hybrid_forward, TOKENS, jnp.bfloat16)
    assert gap > 100 * LOGIT_TOLERANCE


# -- the recurrence's three forms -------------------------------------------------

def recurrence_inputs(seed, rows=2, tokens=50, heads=6, width=8, state=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (rows, tokens, heads, width)),
            jax.nn.softplus(jax.random.normal(keys[1], (rows, tokens, heads))
                            - 2.0),
            jax.random.normal(keys[2], (rows, tokens, state)),
            jax.random.normal(keys[3], (rows, tokens, state)),
            -jnp.exp(jax.random.uniform(keys[4], (heads,), minval=0.0,
                                        maxval=2.7)),
            jax.random.normal(keys[5], (rows, state, heads * width)))


# float32 sums of 50 decayed terms in another order on values of spread 4:
# measured 5e-6; a bfloat16 product reads 1e-2
FORMS_TOLERANCE = 3e-5


@pytest.mark.parametrize("chunk", [16, 128, 7])
def test_the_chunked_form_equals_the_one_token_rule(chunk):
    """Chunks of 16 (three whole and a padded one), one chunk of 50, and a
    length that divides nothing: the same outputs and the same state as 50
    calls of `ssm_step`, from a state that is not zeros."""
    x, dt, b, c, a, state = recurrence_inputs(1)
    want, want_state = M.ssm_plain(x, dt, b, c, a, state)
    out, new = M.ssm_chunked(x, dt, b, c, a, state, chunk=chunk)
    assert np.abs(np.asarray(out - want)).max() < FORMS_TOLERANCE
    assert np.abs(np.asarray(new - want_state)).max() < FORMS_TOLERANCE


def test_two_pieces_equal_one():
    """A prompt's second piece starts from the state the first one left."""
    x, dt, b, c, a, state = recurrence_inputs(2)
    want, want_state = M.ssm_chunked(x, dt, b, c, a, state)
    first, middle = M.ssm_chunked(x[:, :32], dt[:, :32], b[:, :32],
                                  c[:, :32], a, state)
    second, new = M.ssm_chunked(x[:, 32:], dt[:, 32:], b[:, 32:], c[:, 32:],
                                a, middle)
    assert np.abs(np.asarray(jnp.concatenate([first, second], axis=1)
                             - want)).max() < FORMS_TOLERANCE
    assert np.abs(np.asarray(new - want_state)).max() < FORMS_TOLERANCE


def test_a_padded_row_moves_neither_the_state_nor_the_tail():
    """One Mamba layer over a block of two rows, the second live for 9 of
    its 20 positions and the first for none: the first row's S and tail
    come back bit for bit, the second's are what 9 tokens alone leave."""
    config = model_config()
    layer = CASES.params["layers"][0]
    key = jax.random.PRNGKey(3)
    state = tuple(jax.random.normal(jax.random.fold_in(key, n),
                                    (2,) + shape).astype(dtype)
                  for n, (shape, dtype) in enumerate(config.slot_state[0]))
    x = jax.random.normal(jax.random.fold_in(key, 9), (2, 20, 64))
    live = jnp.arange(20)[None] < jnp.asarray([0, 9])[:, None]
    out, after = M._mamba_block(layer, config, x, state, live)
    for before, left in zip(state, after):
        assert np.array_equal(np.asarray(left)[0], np.asarray(before)[0])
    alone, want = M._mamba_block(layer, config, x[1:, :9],
                                 tuple(z[1:] for z in state),
                                 jnp.ones((1, 9), bool))
    assert np.abs(np.asarray(out[1, :9] - alone[0])).max() < 1e-6
    for left, wanted in zip(after, want):
        assert np.abs(np.asarray(left[1] - wanted[0])).max() < 1e-6


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["recurrence", "kernel-interpreted"])
def test_a_slot_that_does_not_decode_keeps_its_states_bits(kernel):
    """One Mamba layer's token mixing in the step over three slots of which
    the middle one decodes nothing: its state S and its convolution tail
    come back bit for bit, the others' change."""
    config = model_config()
    layer = CASES.params["layers"][2]
    key = jax.random.PRNGKey(5)
    state = tuple(jax.random.normal(jax.random.fold_in(key, n),
                                    (3,) + shape).astype(dtype)
                  for n, (shape, dtype) in enumerate(config.slot_state[2]))
    active = jnp.asarray([True, False, True])
    lengths = jnp.zeros((3,), jnp.int32)
    x = jax.random.normal(jax.random.fold_in(key, 9), (3, 1, 64))
    attend = M._step_attention(kernel)
    out, _, after, counted = attend(
        None, layer, config, x, None, None, [], None, [], lengths, lengths,
        0, active, state, active)
    for before, left in zip(state, after):
        assert np.array_equal(np.asarray(left)[1], np.asarray(before)[1])
        assert not np.array_equal(np.asarray(left)[0], np.asarray(before)[0])
    assert np.asarray(counted).tolist() == [2, 3]
    # the live slots' outputs are the other form's
    other, _, left, _ = M._step_attention(not kernel)(
        None, layer, config, x, None, None, [], None, [], lengths, lengths,
        0, active, state, active)
    live = np.asarray(active)
    assert np.abs(np.asarray(out - other)[live]).max() < 1e-5
    assert np.abs(np.asarray(after[0] - left[0])[live]).max() < 1e-5


# -- the written equations against the published implementation -------------------

def test_the_reference_is_the_published_implementation():
    """`GraniteMoeHybridForCausalLM` built from a tiny `GraniteMoeHybridConfig`
    with the reference's own random weights laid into its state dict: the
    one test that ties benchmark/reference/ssm_hybrid_lm.py's equations to
    `transformers`' modeling_granitemoehybrid.py (its torch path: no CUDA
    kernel here).  float32 on both sides: measured 1e-8 on logits of spread
    0.001; a wrong split of in_proj, a norm before the gate, a missing
    multiplier read 1e-4 and more."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    published = transformers.GraniteMoeHybridConfig(
        **{key: value for key, value in SIZES.items()},
        max_position_embeddings=128, pad_token_id=None, bos_token_id=None,
        eos_token_id=None, initializer_range=0.02)
    model = transformers.GraniteMoeHybridForCausalLM(published).eval()
    params = CASES.params

    def t(leaf):
        return torch.tensor(np.asarray(leaf, np.float32))

    state = {"model.embed_tokens.weight": t(params["embed"]["table"]),
             "lm_head.weight": t(params["embed"]["table"]),
             "model.norm.weight": t(params["ln_out"]["scale"])}
    for i, layer in enumerate(params["layers"]):
        at = f"model.layers.{i}."
        state |= {
            at + "input_layernorm.weight": t(layer["ln_attn"]["scale"]),
            at + "post_attention_layernorm.weight":
                t(layer["ln_mlp"]["scale"]),
            at + "shared_mlp.input_linear.weight": t(np.concatenate(
                [layer["gate"]["w"], layer["up"]["w"]], axis=1).T),
            at + "shared_mlp.output_linear.weight": t(layer["down"]["w"].T)}
        if "mamba" in layer:
            mamba = layer["mamba"]
            state |= {
                at + "mamba.in_proj.weight": t(mamba["in"]["w"].T),
                at + "mamba.conv1d.weight":
                    t(mamba["conv"]["w"].T[:, None, :]),
                at + "mamba.conv1d.bias": t(mamba["conv"]["b"]),
                at + "mamba.dt_bias": t(mamba["dt_bias"]),
                at + "mamba.A_log": t(mamba["a_log"]),
                at + "mamba.D": t(mamba["d"]),
                at + "mamba.norm.weight": t(mamba["norm"]["scale"]),
                at + "mamba.out_proj.weight": t(mamba["out"]["w"].T)}
        else:
            state |= {at + f"self_attn.{name}_proj.weight":
                      t(layer["attn"][name]["w"].T) for name in "qkvo"}
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and not [
        name for name in missing if "rotary" not in name], (missing,
                                                            unexpected)
    with torch.no_grad():
        theirs = model(torch.tensor(TOKENS[None].astype(np.int64))
                       ).logits[0].numpy()
    ours = reference_logits(TOKENS)
    assert theirs.std() > 0.0008
    assert np.abs(ours - theirs).max() < LOGIT_TOLERANCE
