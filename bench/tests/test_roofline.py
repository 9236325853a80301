"""The least-bytes function of the solver step and the peaks table."""

import pytest

from bench import roofline


def test_step_bytes_at_the_solo_shape():
    # 2^20 points, d=256, B=128, float32 points: two reads of the
    # (128, 2^20) block, ten 4-byte vectors over the points, and the
    # gather and scatter of 128 entries of w
    block = 2 * 128 * (1 << 20) * 4            # 1_073_741_824
    vectors = 10 * (1 << 20) * 4               # 41_943_040
    w = 2 * 128 * 4                            # 1_024
    assert block + vectors + w == 1_115_685_888
    assert roofline.step_bytes(1 << 20, 256, 128, 1, 4) == 1_115_685_888
    assert roofline.step_bytes(1 << 20, 256, 128, 494, 4) == \
        494 * 1_115_685_888


def test_step_bytes_follows_the_operand_dtype():
    f32 = roofline.step_bytes(1 << 20, 256, 128, 1, 4)
    bf16 = roofline.step_bytes(1 << 20, 256, 128, 1, 2)
    assert f32 - bf16 == 2 * 128 * (1 << 20) * 2


def test_step_bytes_counts_true_points_not_padding():
    assert roofline.step_bytes(11055, 128, 1, 1, 4) == \
        2 * 11055 * 4 + 10 * 11055 * 4 + 2 * 4


def test_block_must_fit_the_dimension():
    with pytest.raises(ValueError):
        roofline.step_bytes(1024, 64, 128, 1, 4)


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def _reader():
    from bench import run as harness
    return harness.reader("step_roofline_pct.solo")


def _ctx(dtype, elements, solve_s=1.0, runs=1):
    from bench import run as harness
    from bench import trace
    s = trace.Summary(busy_s=1.0, window_s=1.0, devices=1,
                      exec_s={"run_solve_slots": solve_s},
                      exec_runs={"run_solve_slots": runs},
                      exec_array={"run_solve_slots": [dtype, elements]},
                      op_s={}, busy=trace.union([]), spans=[], gaps=[])
    cfg = {"n1": 1 << 19, "n2": 1 << 19, "d": 256, "block_size": 128}
    counters = {"steps_traced": 494, "fits_traced": 1}
    return harness.Context(s, counters, cfg, roofline.peaks("TPU v5 lite"))


def test_roofline_reader_counts_the_type_the_program_holds():
    read = _reader()
    n = 1 << 20
    f32 = read(_ctx("f32", 256 * n))
    assert f32 == pytest.approx(
        100 * 494 * 1_115_685_888 / 819e9)
    bf16 = read(_ctx("bf16", 256 * n))
    assert bf16 == pytest.approx(
        100 * 494 * roofline.step_bytes(n, 256, 128, 1, 2) / 819e9)
    assert bf16 < f32


def test_roofline_reader_stays_silent_without_the_operand():
    read = _reader()
    # the largest array named is smaller than the points: not the operand
    assert read(_ctx("f32", 128 * (1 << 20))) is None
    assert read(_ctx("f99", 256 * (1 << 20))) is None
    assert read(_ctx("f32", 256 * (1 << 20), runs=2)) is None
