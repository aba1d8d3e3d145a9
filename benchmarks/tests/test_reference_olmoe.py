"""The OLMoE reference agrees with the program's forward, at a tiny size on
the CPU in float32, as ``test_reference.py`` shows for the other two (the
chip compares at the published widths, ``tools/olmoe_agreement.py``)."""

import jax
import numpy as np
import pytest

from benchmarks.lib.correctness import reference_loss
from benchmarks.lib.manifest import Bench

TINY_OLMOE = dict(vocab_size=503, hidden_size=64, intermediate_size=32,
                  num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
                  max_seq_len=256, num_experts=8, moe_drop_tokens=False,
                  moe_aux_loss_coef=0.0)


@pytest.mark.parametrize("top_k,norm_topk,qk_norm", [
    (2, False, True), (4, False, True), (2, True, True), (2, False, False)])
def test_reference_agrees_with_the_programs_forward(top_k, norm_topk,
                                                    qk_norm):
    from deepspeed_tpu.models import CausalLM, ModelConfig

    ref = Bench().reference("olmoe-1b-7b-L8")
    model = CausalLM(ModelConfig(
        **TINY_OLMOE, num_experts_per_tok=top_k, qk_norm=qk_norm,
        moe_norm_topk_prob=norm_topk), None)
    ref_config = dict(num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, rms_norm_eps=1e-5,
                      rope_theta=10000, num_experts_per_tok=top_k,
                      norm_topk_prob=norm_topk, qk_norm=qk_norm)
    params = model.init(jax.random.PRNGKey(0))
    # norm scales of exactly one would hide a dropped q_norm or k_norm
    noise = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(next(noise), a.shape), params)
    tokens = np.random.default_rng(0).integers(0, 503, (2, 48),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.apply(params, tokens))
        loss = float(model.apply(params, tokens, tokens))
    device = jax.devices()[0]
    got = np.asarray(ref.logits_rows(params, ref_config, tokens[0],
                                     list(range(48)), device))
    # float32 both sides; a token's output is a sum of k expert outputs,
    # which the grouped path adds in another order
    np.testing.assert_allclose(got, logits[0], rtol=2e-4, atol=2e-5)
    assert reference_loss(ref, params, ref_config, tokens, device) == \
        pytest.approx(loss, rel=1e-5)
