"""Every Pallas kernel compiles for a TPU v5e at the widths the chip
smoke runs (one pt-6b-d4 track; the dense-6b KV layout; falcon-mamba-7b
for the SSM scan).

The chip is described, not attached: the TPU compiler installed with
JAX compiles for it and refuses what the chip's compiler would refuse
(block shapes off the (8, 128) tiling, primitives the Mosaic lowering
lacks, VMEM over budget) — none of which interpret mode can see.
Nothing runs, so these tests say nothing about results or times.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import quant_matmul as qm
from repro.kernels import rmsnorm as rn
from repro.kernels import ssm_scan as ss


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: entries compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


BF, F32 = jnp.bfloat16, jnp.float32
B, BS, NMAX, G, HD = 8, 16, 64, 4, 128     # 8 slots of 1,024 tokens


def _paged(kh):
    return (lambda *a: da.paged_decode_attention(*a, interpret=False),
            [((B, kh * G, HD), BF), ((B * NMAX, BS, kh, HD), BF),
             ((B * NMAX, BS, kh, HD), BF), ((B, NMAX), jnp.int32),
             ((B,), jnp.int32)])


CASES = {
    "paged_decode_attention_kh1": _paged(1),
    "paged_decode_attention_kh8": _paged(8),
    "decode_attention": (
        lambda *a: da.decode_attention(*a, interpret=False),
        [((B, G, HD), BF), ((B, NMAX * BS, 1, HD), BF),
         ((B, NMAX * BS, 1, HD), BF), ((B,), jnp.int32)]),
    "flash_attention": (
        lambda *a: fa.flash_attention(*a, interpret=False),
        [((1, 2048, G, HD), BF)] * 3),
    "int8_matmul": (
        lambda *a: qm.int8_matmul(*a, interpret=False),
        [((256, 1408), BF), ((1408, 3968), jnp.int8), ((1, 3968), F32)]),
    "rmsnorm": (
        lambda *a: rn.rmsnorm(*a, interpret=False),
        [((2048, 1408), BF), ((1408,), F32)]),
    "ssm_scan": (
        lambda *a: ss.ssm_scan(*a, interpret=False),
        [((1, 1024, 8192, 16), F32)] * 2 + [((1, 8192, 16), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    kernel, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(kernel).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, name
