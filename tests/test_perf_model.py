"""The paper's performance model (Eq. 1-4): reproduce its own numbers."""
import numpy as np
import pytest

from repro.core import perf_model as PM


def test_code_balance_dp_matches_eq1():
    # B_W^DP = 6 + 4*alpha + 8/N_nzr  (paper Eq. 1)
    for alpha in (0.1, 0.5, 1.0):
        for n in (7, 15, 123):
            assert PM.code_balance(alpha, n, value_bytes=8) == pytest.approx(
                6 + 4 * alpha + 8 / n)


def test_code_balance_sp():
    # SP: (4+4+4a+8/N)/2 = 4 + 2a + 4/N
    assert PM.code_balance(0.5, 16, value_bytes=4) == pytest.approx(
        4 + 1.0 + 0.25)


def test_alpha_range():
    lo, hi = PM.alpha_range(15)
    assert lo == pytest.approx(1 / 15) and hi == 1.0


def test_eq3_paper_numbers():
    """Paper §2.2: alpha=1/N_nzr and B_GPU ~ 20*B_PCI -> N_nzr <= 25;
    alpha=1, B_GPU ~ 10*B_PCI -> N_nzr <= 7."""
    # worst case: alpha = 1/n, solve self-consistently like the paper
    # (they use alpha ~ 0 in the denominator: 2*19/1.5 ~ 25)
    n = PM.n_nzr_upper_for_link_penalty(20.0, 1.0, alpha=0.08)
    assert 24 <= n <= 26
    n2 = PM.n_nzr_upper_for_link_penalty(10.0, 1.0, alpha=1.0)
    assert 7 <= n2 <= 7.3


def test_eq4_paper_numbers():
    """Paper: B_GPU ~ 10*B_PCI, alpha=1 -> N_nzr >= 80 sufficient;
    B_GPU ~ 20*B_PCI, alpha ~ 0 -> N_nzr >= 266."""
    n = PM.n_nzr_lower_for_link_penalty(10.0, 1.0, alpha=1.0)
    assert 79 <= n <= 80
    n2 = PM.n_nzr_lower_for_link_penalty(20.0, 1.0, alpha=0.0)
    assert 264 <= n2 <= 266


def test_paper_conclusion_hmep_samg_not_worthwhile():
    """Paper §3: HMEp (N_nzr~15) and sAMG (~7) fall below the Eq. 3
    threshold for the paper's hardware ratio -> no accelerator benefit."""
    thresh = PM.n_nzr_upper_for_link_penalty(20.0, 1.0, alpha=0.08)
    assert 15 < thresh and 7 < thresh          # both below threshold
    # DLR/UHBR (123-315) are clear of the 50%-penalty region
    assert 123 > thresh and 315 > thresh


def test_tpu_thresholds_documented():
    """Same analysis with TPU v5e numbers: HBM 819 GB/s vs ICI 50 GB/s/link
    gives ratio ~16 -> N_nzr <= ~19 is link-dominated."""
    spec = PM.TPU_V5E
    n = PM.n_nzr_upper_for_link_penalty(spec.hbm_bw, spec.ici_bw, alpha=0.1)
    assert 15 < n < 25


def test_t_mvm_t_link_crossover():
    n_rows = 1e6
    t_m = PM.t_mvm(n_rows, n_nzr=100, alpha=0.1, dev_bw=819e9)
    t_l = PM.t_link(n_rows, link_bw=50e9)
    assert t_m > t_l  # large N_nzr: compute dominates the link
    t_m2 = PM.t_mvm(n_rows, n_nzr=5, alpha=0.1, dev_bw=819e9)
    assert t_m2 < 3 * t_l


def test_t_link_gathered_prices_measured_halo():
    """The gathered-halo link term charges only the referenced entries:
    it agrees with t_link when the whole slice is referenced (plus the
    LHS return leg t_link also counts) and vanishes for block-diagonal
    partitions."""
    n_loc, link = 10_000, 50e9
    # halo == full slice in both directions ~ the t_link regime
    full = PM.t_link_gathered(2 * n_loc, link, value_bytes=8)
    assert full == pytest.approx(PM.t_link(n_loc, link, value_bytes=8))
    # measured coupling of 80 entries: 2*n_loc/80 = 250x cheaper
    sparse = PM.t_link_gathered(80, link, value_bytes=8)
    assert sparse * 250 == pytest.approx(full)
    assert PM.t_link_gathered(0, link) == 0.0
    # multi-RHS scales linearly
    assert PM.t_link_gathered(80, link, k=4) == pytest.approx(4 * sparse)


def test_t_link_gathered_msgs_term():
    """The per-message fixed cost and the link bandwidth scale only act
    through an installed/passed calibration; the old positional
    signature (no msgs, no calibration) is unchanged."""
    link = 50e9
    plain = PM.t_link_gathered(80, link, value_bytes=8)
    # msgs without calibration: fixed cost is 0, nothing changes
    assert PM.t_link_gathered(80, link, value_bytes=8, msgs=4,
                              calibration=None) == pytest.approx(plain)
    cal = PM.Calibration(bw_scale=1.0, link_bw_scale=0.5,
                         msg_overhead_s={"gathered": 25e-6, "full": 5e-6})
    got = PM.t_link_gathered(80, link, value_bytes=8, msgs=4,
                             halo="gathered", calibration=cal)
    assert got == pytest.approx(8 * 80 / (link * 0.5) + 4 * 25e-6)
    # the full flavour pays its own (cheaper) per-message cost
    got_f = PM.t_link_gathered(80, link, value_bytes=8, msgs=4,
                               halo="full", calibration=cal)
    assert got_f == pytest.approx(8 * 80 / (link * 0.5) + 4 * 5e-6)
    # unknown halo key costs 0 fixed (data-sheet behaviour)
    assert PM.t_link_gathered(80, link, value_bytes=8, msgs=4,
                              halo="exotic", calibration=cal) \
        == pytest.approx(8 * 80 / (link * 0.5))


def test_calibration_link_fields_validate():
    with pytest.raises(ValueError):
        PM.Calibration(bw_scale=1.0, link_bw_scale=0.0)
    with pytest.raises(ValueError):
        PM.Calibration(bw_scale=1.0, link_bw_scale=-2.0)
    cal = PM.Calibration(bw_scale=1.0)
    assert cal.link_bw_scale == 1.0 and dict(cal.msg_overhead_s) == {}


def _banded_partition(halo_w=1, n=256, n_dev=4, reach=None):
    """Diagonal plus a strided off-band: only every 4th row couples
    across the device boundary, so the gathered halo is genuinely
    smaller than the full neighbor slice."""
    from repro.core import dist_spmv as D, formats as F
    reach = reach if reach is not None else 64 * halo_w
    rows, cols, vals = [], [], []
    for r in range(n):
        offs = (r - reach, r, r + reach) if r % 4 == 0 else (r,)
        for c in offs:
            if 0 <= c < n:
                rows.append(r), cols.append(c), vals.append(1.0 + r + c)
    m = F.csr_from_coo(np.array(rows), np.array(cols),
                       np.array(vals, np.float32), (n, n))
    return D.partition_csr(m, n_dev, b_r=32)


def test_choose_halo_crossover():
    """Without a calibration the gathered exchange's byte advantage wins;
    a calibration pricing the gathered per-message set-up flips the
    decision — the measured toy-scale behaviour."""
    dist = _banded_partition(halo_w=1)
    assert dist.halo_w >= 1
    g_bytes = dist.comm_bytes_per_device(halo="gathered")
    f_bytes = dist.comm_bytes_per_device(halo="full")
    assert g_bytes < f_bytes
    assert PM.choose_halo(dist, calibration=None) == "gathered"
    pricey = PM.Calibration(bw_scale=1.0,
                            msg_overhead_s={"gathered": 1e-2})
    assert PM.choose_halo(dist, calibration=pricey) == "full"


def test_choose_halo_tie_goes_gathered():
    # block-diagonal: halo_w == 0, nothing crosses the wire either way
    from repro.core import dist_spmv as D, formats as F
    blk = np.kron(np.eye(4, dtype=np.float32),
                  np.arange(1, 65 * 64 + 1, dtype=np.float32)[:64 * 64]
                  .reshape(64, 64))
    dist = D.partition_csr(F.csr_from_dense(blk), 4, b_r=32)
    assert dist.halo_w == 0
    assert PM.choose_halo(dist, calibration=None) == "gathered"


def test_predicted_dist_overlap_hides_comm():
    """Bulk-synchronous modes serialize compute after comm; the
    overlapped modes charge max(local, comm) + remote, so they can
    never predict slower."""
    dist = _banded_partition(halo_w=1)
    for halo in ("gathered", "full"):
        t_bulk = PM.predicted_dist_spmv_seconds(
            dist, halo, "vector", calibration=None)
        t_ovl = PM.predicted_dist_spmv_seconds(
            dist, halo, "overlap", calibration=None)
        t_pipe = PM.predicted_dist_spmv_seconds(
            dist, halo, "pipeline", calibration=None)
        assert 0 < t_ovl <= t_bulk
        assert t_pipe == pytest.approx(t_ovl)
    # multi-RHS scales the wire term
    t1 = PM.predicted_dist_spmv_seconds(dist, "gathered", "vector",
                                        calibration=None)
    t4 = PM.predicted_dist_spmv_seconds(dist, "gathered", "vector", k=4,
                                        calibration=None)
    assert t4 > t1


def test_roofline_terms():
    r = PM.roofline_terms(hlo_flops=1e15, hlo_bytes=1e13,
                          collective_bytes=1e11, chips=256)
    assert r.compute_s == pytest.approx(1e15 / (256 * 197e12))
    assert r.memory_s == pytest.approx(1e13 / (256 * 819e9))
    assert r.collective_s == pytest.approx(1e11 / (256 * 50e9))
    assert r.dominant in ("compute", "memory", "collective")


def test_spmvm_bytes_model():
    b = PM.spmvm_bytes(stored_elements=1000, n_rows=100, alpha=1.0,
                       n_nzr=10, value_bytes=8)
    assert b == 1000 * 12 + 1.0 * 10 * 100 * 8 + 2 * 100 * 8


def test_gathered_elements_priced_per_element():
    """An XLA gather costs ``gather_s`` per element, whatever its bytes;
    a windowed format's RHS bytes replace the gathered stream's."""
    kw = dict(stored_elements=10_000, n_rows=1_000, n_nzr=10.0,
              calibration=None)
    base = PM.predicted_spmv_seconds(**kw)
    assert PM.predicted_spmv_seconds(**kw, gathered=10_000) - base == \
        pytest.approx(PM.gather_seconds(10_000))
    assert PM.gather_seconds(10_000) == pytest.approx(
        10_000 * PM.TPU_V5E.gather_s)
    win = PM.window_rhs_bytes(n_blocks=8, window=3072)
    assert win == 8 * 3072 * 4
    assert PM.predicted_spmv_seconds(**kw, rhs_bytes=win) - base == \
        pytest.approx((win - PM.gathered_rhs_bytes(10_000))
                      / PM.TPU_V5E.hbm_bw)
