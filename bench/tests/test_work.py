"""Operation and byte counts against hand counts."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import work  # noqa: E402


def test_forward_ops_gpt2_small():
    # d 768, 12 heads of 64, dff 3072, seq 8 (multiply-adds):
    #   q, k, v    3 * 768 * 768 * 8   = 14,155,776
    #   output         768 * 768 * 8   =  4,718,592
    #   qk^T, P v  2 * 12 * 8 * 8 * 64 =     98,304
    #   MLP        2 * 768 * 3072 * 8  = 37,748,736
    block = {"d": 768, "dff": 3072, "heads": 12, "dh": 64, "seq": 8}
    assert work.forward_ops(block) == 2 * 56_721_408


def test_forward_ops_d128():
    #   q, k, v    3 * 128 * 128 * 8   =    393,216
    #   output         128 * 128 * 8   =    131,072
    #   qk^T, P v  2 * 4 * 8 * 8 * 32  =     16,384
    #   MLP        2 * 128 * 512 * 8   =  1,048,576
    block = {"d": 128, "dff": 512, "heads": 4, "dh": 32, "seq": 8}
    assert work.forward_ops(block) == 2 * 1_589_248


def test_hlo_bytes_reads_operands_and_results_once():
    # a d=768 opening row: u32[1024, 4096] in, u32[4096] out, Montgomery
    hlo = ('%custom-call.3 = u32[4096]{0:T(1024)} custom-call('
           'u32[1024,4096]{1,0:T(8,128)} %p0, u32[1024]{0} %p1), '
           'custom_call_target="tpu_custom_call"')
    assert work.hlo_bytes(hlo) == 4 * (4096 + 1024 * 4096 + 1024)


def test_hlo_bytes_tuple_results():
    # a d=128 sum-check round: (K=3, 4, R=8, 128) factors in, a tuple of
    # folded factors and a (3, 16) sponge state out
    hlo = ('%custom-call.1 = (u32[3,4,4,128]{3,2,1,0}, u32[3,16]{1,0}) '
           'custom-call(u32[3,4,8,128]{3,2,1,0} %f, u32[3,16]{1,0} %s), '
           'custom_call_target="tpu_custom_call", backend_config="{}"')
    assert work.hlo_bytes(hlo) == 4 * (3 * 4 * 4 * 128 + 48
                                       + 3 * 4 * 8 * 128 + 48)


def test_shape_bytes():
    assert work.shape_bytes("bf16[2,3] s8[5] pred[] f32[0]") == 12 + 5 + 1
