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


def _pt_tracks(*a):
    """The pt-6b-d4 cell's call: 8 tracks of 1 KV head under the track
    vmap, folded into one kernel call of 8 KV heads."""
    q, k, v, table, lengths = a
    return jax.vmap(lambda q, k, v: da.paged_decode_attention(
        q, k, v, table, lengths, interpret=False))(q, k, v)


CASES = {
    "paged_decode_attention_kh1": _paged(1),
    "paged_decode_attention_kh8": _paged(8),
    # the cells' shapes: 160 blocks of 16 a slot; 12 pt-6b-d4 slots,
    # 7 dense-6b slots
    "paged_decode_attention_pt_cell": (
        _pt_tracks,
        [((8, 12, G, HD), BF), ((8, 641, BS, 1, HD), BF),
         ((8, 641, BS, 1, HD), BF), ((12, 160), jnp.int32),
         ((12,), jnp.int32)]),
    "paged_decode_attention_dense_cell": (
        lambda *a: da.paged_decode_attention(*a, interpret=False),
        [((7, 8 * G, HD), BF), ((353, BS, 8, HD), BF),
         ((353, BS, 8, HD), BF), ((7, 160), jnp.int32), ((7,), jnp.int32)]),
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


def test_pt_decode_step_streams_live_kv_on_v5e(one_chip, monkeypatch):
    """pt-6b-d4's decode program at the cell's slots and pool (one track
    block of 4 layers), compiled as the chip's backend selects it: the
    paged kernel reads the pools, no [12, 2560, ...] view of every
    slot's capacity is gathered, and no layer's pool is relaid out."""
    import re
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch import steps as steps_lib
    from repro.serving.engine import ModelRunner

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config("pt-6b-d4").replace(n_layers=4)
    fns = steps_lib.model_fns(cfg)
    p_shape = jax.eval_shape(lambda k: fns["init"](k, cfg),
                             jax.random.PRNGKey(0))
    small, blocks = 6, 641
    r = ModelRunner(cfg, p_shape, max_slots=12, max_seq_len=2560,
                    block_size=BS, num_blocks=small, prefill_chunk=512)
    assert r.decode_attn == "paged_kernel"

    def spec(x, resize=False):
        shape = tuple(blocks if resize and d == small + 1 else d
                      for d in x.shape)
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    cache = jax.tree_util.tree_map(lambda x: spec(x, True), r.cache)
    params = jax.tree_util.tree_map(spec, p_shape)
    i32 = jnp.int32

    def s(dt, *shape):
        return jax.ShapeDtypeStruct(shape or (12,), dt, sharding=one_chip)

    text = r._decode.lower(
        params, cache, s(i32), s(i32), s(jnp.bool_), s(i32, 12, 160),
        s(jnp.uint32), s(i32), s(F32), s(i32), s(F32), s(i32),
        s(i32)).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\[12,2560,", text)
    pool_copies = re.findall(r"= bf16\[\d+,\d+,8,641,16,1,128\]\S* copy\(",
                             text)
    # the scan writes its new cache, then one copy of each pool into the
    # program's output: nothing per layer
    assert len(pool_copies) <= 2, pool_copies
