"""HLO parser units (handcrafted HLO text) + roofline term math."""
import numpy as np

from repro.common import hw
from repro.roofline import hlo
from repro.roofline.analysis import model_flops, model_n_params

_HLO = """\
HloModule jit_step, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body (p: (s32[], f32[16,64])) -> (s32[], f32[16,64]) {
  %p = (s32[], f32[16,64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[16,64]{1,0} get-tuple-element(%p), index=1
  %w = f32[64,64]{1,0} constant({...})
  %dot.1 = f32[16,64]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[16,64]{1,0} all-reduce(%dot.1), replica_groups=[1,8]<=[8], to_apply=%add
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[16,64]) tuple(%i2, %ar)
}

%cond (p: (s32[], f32[16,64])) -> pred[] {
  %p = (s32[], f32[16,64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[16,64]) -> f32[16,64] {
  %x = f32[16,64]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[16,64]) tuple(%z, %x)
  %w2 = (s32[], f32[16,64]) while(%t0), condition=%cond, body=%body
  %ag = f32[128,64]{1,0} all-gather(%x), replica_groups=[1,8]<=[8], dimensions={0}
  ROOT %out = f32[16,64]{1,0} get-tuple-element(%w2), index=1
}
"""


def test_parser_expands_while_by_condition_constant():
    res = hlo.analyze_text(_HLO, 8)
    # dot: 2*16*64*64 flops, 5 iterations
    np.testing.assert_allclose(res["flops"], 2 * 16 * 64 * 64 * 5)
    # all-reduce: 2*(7/8)*16*64*4 bytes wire, 5 iterations
    ar = 2 * (7 / 8) * 16 * 64 * 4 * 5
    np.testing.assert_allclose(res["all-reduce"], ar)
    assert res["all-reduce_count"] == 5
    # all-gather result 128*64*4 bytes, (7/8) factor, once
    np.testing.assert_allclose(res["all-gather"], (7 / 8) * 128 * 64 * 4)
    np.testing.assert_allclose(res["total"],
                               ar + (7 / 8) * 128 * 64 * 4)


def test_parser_known_trip_count_overrides():
    txt = _HLO.replace(
        "body=%body", 'body=%body, backend_config={"known_trip_count":{"n":"3"}}')
    res = hlo.analyze_text(txt, 8)
    np.testing.assert_allclose(res["flops"], 2 * 16 * 64 * 64 * 3)


def test_loop_all_reduces_reads_tpu_layouts():
    """One entry per while loop: trips x all-reduces per iteration.  TPU
    HLO writes tile layouts with parens inside tuple types; the loop must
    still be found."""
    want = [{"trips": 5, "all_reduces": 1, "group_sizes": [8]}]
    assert hlo.loop_all_reduces(_HLO, 8) == want
    tpu = _HLO.replace(
        "%w2 = (s32[], f32[16,64]) while(",
        "%w2 = (s32[]{:T(128)}, f32[16,64]{1,0:T(8,128)S(1)}) while(")
    assert "T(8,128)" in tpu
    assert hlo.loop_all_reduces(tpu, 8) == want
    np.testing.assert_allclose(hlo.analyze_text(tpu, 8)["flops"],
                               2 * 16 * 64 * 64 * 5)


def test_wire_bytes_formulas():
    assert hlo._wire_bytes("all-reduce", 100, 4) == 2 * 0.75 * 100
    assert hlo._wire_bytes("all-gather", 100, 4) == 0.75 * 100
    assert hlo._wire_bytes("reduce-scatter", 25, 4) == 75
    assert hlo._wire_bytes("all-to-all", 100, 4) == 75
    assert hlo._wire_bytes("collective-permute", 100, 4) == 100
    assert hlo._wire_bytes("all-reduce", 100, 1) == 0


def test_model_flops_dense_vs_moe():
    from repro.configs import get_config
    dense = get_config("tinyllama-1.1b")
    n = model_n_params(dense)
    assert abs(n - 1.1e9) / 1.1e9 < 0.05
    from repro.common.types import SHAPES_BY_NAME
    tf = model_flops(dense, SHAPES_BY_NAME["train_4k"])
    np.testing.assert_allclose(tf, 6 * n * 256 * 4096, rtol=1e-6)

    moe = get_config("deepseek-v3-671b")
    total = model_n_params(moe, active=False)
    active = model_n_params(moe, active=True)
    assert abs(total - 671e9) / 671e9 < 0.03
    assert abs(active - 37e9) / 37e9 < 0.15      # ~37B active
    df = model_flops(moe, SHAPES_BY_NAME["decode_32k"])
    np.testing.assert_allclose(df, 2 * active * 128, rtol=1e-6)


def test_shape_bytes_tuple_types():
    assert hlo._type_bytes("(s32[], f32[16,8]{1,0})") == 4 + 16 * 8 * 4
    assert hlo._type_bytes("bf16[2,3,4]{2,1,0}") == 48
