"""ECM model validation: the implementation must REPRODUCE the paper's own
published predictions (§3, Table 2) from first principles."""

import pytest

from repro.core import ecm


def test_ivb_naive_matches_paper_eq2():
    r = ecm.ecm_x86(ecm.IVB, ecm.NAIVE_SP)
    assert r.pred_cy[:3] == (4, 8, 12)
    assert abs(r.pred_cy[3] - 21.0) < 0.1
    assert r.perf_gups == (8.80, 4.40, 2.93, 1.68)
    assert r.n_s == 4
    assert abs(r.p_bw_gups - 5.76) < 0.01


def test_ivb_kahan_scalar_matches_paper():
    r = ecm.ecm_x86(ecm.IVB, ecm.KAHAN_SCALAR_SP)
    assert r.t_ol == 64 and r.t_nol == 16
    assert r.pred_cy == (64, 64, 64, 64)
    assert r.perf_gups == (0.55,) * 4
    assert r.n_s == 11  # cannot saturate the 10-core chip


def test_ivb_kahan_sse_matches_paper():
    r = ecm.ecm_x86(ecm.IVB, ecm.KAHAN_SSE_SP)
    assert r.pred_cy[:3] == (16, 16, 16)
    assert r.perf_gups[:3] == (2.20, 2.20, 2.20)
    assert r.perf_gups[3] == 1.68


def test_ivb_kahan_avx_matches_paper():
    r = ecm.ecm_x86(ecm.IVB, ecm.KAHAN_AVX_SP)
    assert r.pred_cy[:3] == (8, 8, 12)
    assert r.perf_gups == (4.40, 4.40, 2.93, 1.68)
    assert r.n_s == 4


def test_dp_scalar_saturates_at_six_cores():
    r = ecm.ecm_x86(ecm.IVB, ecm.KAHAN_SCALAR_DP)
    assert r.pred_cy == (32, 32, 32, 32)
    assert r.n_s == 6
    assert abs(ecm.IVB.load_bw_gbs / 16 - 2.88) < 0.01  # paper's P_BW DP


@pytest.mark.parametrize("machine,expect", [
    (ecm.SNB, (5.40, 5.40, 3.60, 1.73)),
    (ecm.HSW, (4.60, 4.60, 3.86, 1.44)),
    (ecm.BDW, (3.60, 3.60, 3.60, 1.80)),
])
def test_table2_cross_architecture(machine, expect):
    r = ecm.ecm_x86(machine, ecm.KAHAN_AVX_SP)
    for got, want in zip(r.perf_gups, expect):
        assert abs(got - want) < 0.05, (machine.name, r.perf_gups)


def test_multicore_scaling_saturates():
    base = ecm.ecm_x86(ecm.IVB, ecm.KAHAN_AVX_SP)
    p1 = ecm.multicore_scaling(ecm.IVB, ecm.KAHAN_AVX_SP, 1)
    p10 = ecm.multicore_scaling(ecm.IVB, ecm.KAHAN_AVX_SP, 10)
    assert p1 == base.perf_gups[3]
    assert p10 == base.p_bw_gups  # saturated at the bandwidth roof
    # scalar never saturates on 10 cores
    p10s = ecm.multicore_scaling(ecm.IVB, ecm.KAHAN_SCALAR_SP, 10)
    assert p10s < ecm.ecm_x86(ecm.IVB, ecm.KAHAN_SCALAR_SP).p_bw_gups


# --- TPU adaptation: the paper's headline results must transfer -----------

def test_tpu_kahan_comes_for_free_in_hbm():
    naive = ecm.ecm_tpu(ecm.TPU_V5E, ecm.NAIVE_DOT_TPU)
    kahan = ecm.ecm_tpu(ecm.TPU_V5E, ecm.KAHAN_DOT_TPU)
    assert naive.bound == "bandwidth" and kahan.bound == "bandwidth"
    assert naive.perf_db_gups == kahan.perf_db_gups  # "for free"
    assert kahan.n_s_equiv == 1


def test_tpu_sequential_kahan_is_compute_bound():
    seq = ecm.ecm_tpu(ecm.TPU_V5E, ecm.KAHAN_DOT_SEQ_TPU)
    assert seq.bound == "compute"
    assert seq.perf_db_gups < 1.0  # catastrophic, like the paper's scalar
    assert seq.n_s_equiv > 100


def test_tpu_dot2_also_free():
    dot2 = ecm.ecm_tpu(ecm.TPU_V5E, ecm.DOT2_TPU)
    assert dot2.bound == "bandwidth"  # even 17 flops/elem hides under HBM
