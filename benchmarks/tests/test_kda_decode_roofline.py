"""``kernel.kda_decode_roofline`` on hand-made contexts: the kernel's
bytes over its seconds at the peak, never above 100 where the kernel
moves at most the bytes counted, and nothing where the program steps the
state without the kernel."""

import pytest

from harness import spec

BENCH = spec.load_benchmark()
CFG = spec.config_of(BENCH, "solar_open2_250b_ep8")


def _ctx(custom_calls, active_rows=1260):
    # ten decode steps of 128 rows, ``active_rows`` of them active, in a
    # 10 s window; the trace is one second of it: one step traced
    return {"summary": {"bucket_util": {"decode@128": {
                "steps": 10, "rows": 1280, "active_rows": active_rows,
                "wall_s": 0.3}}},
            "config": CFG, "window_s": 10.0,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"window_s": 1.0, "busy_s": 0.9,
                      "ops": dict(custom_calls, **{"fusion.1:f32[8]": 0.3}),
                      "custom_calls": custom_calls}}


def test_the_share_counts_the_active_rows_state_twice_a_layer():
    read = spec.reader_of("kernel.kda_decode_roofline")
    calls = {"kda_decode.3:f32[3,129,64,128,128]": 0.003,
             "kda_decode:f32[3,129,64,128,128]": 0.002,
             "ssd_decode:f32[36,65,64,64,128]": 0.5,
             "custom-call.2:f32[128,8]": 0.5}
    # 3 layers x 126 rows x (64 x 128 x 128 float32) in and out, once
    need = 3 * 2 * 126 * 64 * 128 * 128 * 4
    assert read(_ctx(calls)) == pytest.approx(100 * need / (0.005 * 819e9))


def test_the_share_stays_under_100_when_every_row_moves_at_the_peak():
    """All 128 rows active and the kernel's seconds exactly what their
    bytes take at the peak: 100; the trash slot's rows of a step with
    inactive rows add seconds and no counted bytes, so the share falls."""
    read = spec.reader_of("kernel.kda_decode_roofline")
    t = 3 * 2 * 128 * 64 * 128 * 128 * 4 / 819e9
    calls = {"kda_decode.1:f32[3,129,64,128,128]": t}
    assert read(_ctx(calls, active_rows=1280)) == pytest.approx(100.0)
    assert read(_ctx(calls, active_rows=1200)) < 100.0


@pytest.mark.parametrize("calls", [{}, {"custom-call.4:f32[8]": 0.01},
                                   {"ssd_decode.3:f32[36,65]": 0.01}])
def test_no_share_without_the_kernel(calls):
    read = spec.reader_of("kernel.kda_decode_roofline")
    assert read(_ctx(calls)) is None
    assert read({}) is None
