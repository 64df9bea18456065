"""The Pallas kernels compiled by the TPU compiler for a described v5e chip.

Interpret mode on the CPU validates a kernel's arithmetic but not whether
Mosaic accepts its tiling, slicing and VMEM use; these tests lower each
kernel at the widths of the paper's models for a ``v5e:2x2`` topology that
is described, not attached, and check that the compiled program holds the
kernel as a ``tpu_custom_call``.  Nothing runs.

The topology is described inside a module-scoped fixture only: the TPU
library may be loaded by one process at a time, so describing it at import
(or in a ``skipif`` / ``parametrize`` argument) would make every pytest
worker try to load it while collecting.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.skip_matmul.kernel import skip_concat_matmul_fwd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("width", [2560, 2048], ids=["uvit_h", "hunyuan_dit"])
def test_skip_concat_matmul_compiles_for_v5e(one_chip, width):
    """Decoder skip-in at UViT-H (D=N=2560) and Hunyuan-DiT (D=N=2048)
    width, bf16, 2048 rows."""
    h = jax.ShapeDtypeStruct((2048, width), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2 * width, width), jnp.bfloat16,
                             sharding=one_chip)
    text = _compiled_text(
        lambda h, s, w: skip_concat_matmul_fwd(h, s, w), h, h, w)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    """Forward at Hunyuan-DiT's 1,024 latent tokens (64x64 latent, patch
    2), 16 heads of head_dim 128, bf16, non-causal."""
    q = jax.ShapeDtypeStruct((16, 1024, 128), jnp.bfloat16,
                             sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=False), q, q, q)
    assert "tpu_custom_call" in text
