"""Build a configuration's model through the package's public classes, with
seeded weights made on the device."""

from __future__ import annotations

from typing import Any, Dict


def build_model(config: Dict[str, Any], mesh):
    """``CausalLM(ModelConfig(**fields), mesh)`` from the configuration
    file's ``model_config`` group: no preset of the program is involved."""
    from deepspeed_tpu.models import CausalLM, ModelConfig

    return CausalLM(ModelConfig(**config["model_config"]), mesh)


def seeded_serving_weights(model, seed: int):
    """bf16 weights from the seed in ONE jitted init-and-cast: the fp32
    tree (twice the size) is never resident and nothing crosses the host."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)))(
            jax.random.PRNGKey(seed))
