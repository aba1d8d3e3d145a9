"""What the two files that compile for a described v5e share
(``test_tpu_compile.py``: one kernel a case at advertised widths;
``test_tpu_compile_cells.py``: whole programs of the benchmark's cells): the
device they compile for and the widths they name.  Under ``--dist loadfile``
a file is the unit of work, so the two kinds of case are two files.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32

# D, heads, kv heads, head dim, FFN dim, vocab, gated MLP, norm kind
WIDTHS = {
    "gpt2-small": dict(D=768, H=12, Hkv=12, Dh=64, F=3072, V=50257,
                       glu=False, kind="layernorm"),
    "gpt2-xl": dict(D=1600, H=25, Hkv=25, Dh=64, F=6400, V=50257,
                    glu=False, kind="layernorm"),
    "d4096-gqa8": dict(D=4096, H=32, Hkv=8, Dh=128, F=14336, V=32000,
                       glu=True, kind="rmsnorm"),
}
SEQ = 1024


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one described v5e device.  The persistent compile cache
    is off around the module: an AOT compile for an absent chip is written
    to it but cannot be read back, and the next one warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
