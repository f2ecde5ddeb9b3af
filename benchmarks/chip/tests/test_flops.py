"""Kernel cost models against hand-counted shapes."""
import pytest

import flops


def test_parse_custom_call_reads_kernel_and_types():
    # A line of a scheduled TPU module, as the compiled executables of
    # the solve path print it (backend_config cut short).
    line = ('  %qmv.33 = f32[8,512,1]{2,1,0:T(8,128)S(1)} custom-call('
            '%bitcast.617, %get-tuple-element.14044, %bitcast.616), '
            'custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={s32[8,1,5]{2,1,0}, '
            'f32[8,512,512]{2,1,0}, f32[8,1,512]{2,1,0}}, '
            'frontend_attributes={kernel_metadata={}}, metadata={op_name='
            '"jit(_gmres_ir_batch_jit)/vmap()/while/body/jit(qmv_pallas)/'
            'qmv/pallas_call" stack_frame_id=315}, backend_config={}')
    inst, k, res, ops = flops.parse_custom_call(line)
    assert (inst, k) == ("qmv.33", "qmv")
    assert res == [("f32", (8, 512, 1))]
    assert ops == [("s32", (8, 1, 5)), ("f32", (8, 512, 512)),
                   ("f32", (8, 1, 512))]


@pytest.mark.parametrize("kernel,res,ops,want", [
    # 8 rows of a 512 x 512 matvec: 2 * 8 * 512 * 512 flops; A, v, y
    # and the 8 x 5 format table once each, 4 bytes a value.
    ("qmv", [("f32", (8, 512, 1))],
     [("s32", (8, 1, 5)), ("f32", (8, 512, 512)), ("f32", (8, 1, 512))],
     (4194304.0, 4 * (8 * 512 + 8 * 5 + 8 * 512 * 512 + 8 * 512))),
    # (8, 256, 128) @ (8, 128, 256): 2 * 8 * 256 * 128 * 256 flops.
    ("qmatmul", [("f32", (8, 256, 256))],
     [("s32", (8, 1, 5)), ("f32", (8, 256, 128)), ("f32", (8, 128, 256))],
     (2.0 * 8 * 256 * 128 * 256,
      4 * (8 * 256 * 256 + 8 * 5 + 8 * 256 * 128 + 8 * 128 * 256))),
    # A 512 x 512 factor in 4 column blocks of 128, 8 rows: 8 * 512^2.
    ("trisolve", [("f32", (8, 4, 128))],
     [("s32", (8, 1, 4)), ("f32", (8, 4, 512, 128)), ("f32", (8, 512, 1))],
     (8.0 * 512 * 512,
      4 * (8 * 512 + 8 * 4 + 8 * 512 * 512 + 8 * 512))),
    # Rounding 8 x 64 x 128 values: no flops, read and write once.
    ("chop", [("f32", (8, 64, 128))],
     [("s32", (8, 1, 4)), ("f32", (8, 64, 128))],
     (0.0, 4 * (2 * 8 * 64 * 128 + 8 * 4))),
])
def test_cost_matches_hand_count(kernel, res, ops, want):
    assert flops.cost(kernel, res, ops) == want


def test_unknown_kernel_is_an_error():
    with pytest.raises(KeyError):
        flops.cost("flash", [], [])
