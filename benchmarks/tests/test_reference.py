"""Each plain reference agrees with the program's forward, at a tiny size
on the CPU in float32 (the chip compares at the published widths, outside
the timed window)."""

import jax
import numpy as np
import pytest

from benchmarks.lib.correctness import reference_loss
from benchmarks.lib.manifest import Bench

from conftest import TINY_GPT2, TINY_MISTRAL

CASES = {
    "gpt2-xl": (
        dict(TINY_GPT2, norm="layernorm", activation="gelu", glu=False,
             position="learned", tie_embeddings=True, use_bias=True),
        dict(n_layer=2, n_head=4, layer_norm_epsilon=1e-5)),
    "mistral-7b-L8": (
        dict(TINY_MISTRAL, rope_theta=1e6),
        dict(num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=1e6)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_agrees_with_the_programs_forward(name):
    from deepspeed_tpu.models import CausalLM, ModelConfig

    fields, ref_config = CASES[name]
    ref = Bench().reference(name)
    model = CausalLM(ModelConfig(**fields), None)
    params = model.init(jax.random.PRNGKey(0))
    # zero-initialised biases would hide a dropped bias
    noise = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree.map(
        lambda a: a + 0.02 * jax.random.normal(next(noise), a.shape), params)
    tokens = np.random.default_rng(0).integers(0, 503, (2, 48),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.apply(params, tokens))
        loss = float(model.apply(params, tokens, tokens))
    device = jax.devices()[0]
    got = np.asarray(ref.logits_rows(params, ref_config, tokens[0],
                                     list(range(48)), device))
    # float32 both sides, sums in another order
    np.testing.assert_allclose(got, logits[0], atol=2e-5)
    assert reference_loss(ref, params, ref_config, tokens, device) == \
        pytest.approx(loss, rel=1e-5)
