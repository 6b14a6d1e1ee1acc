"""``kernel.ssd_decode_roofline`` on hand-made contexts: the kernel's
bytes over its seconds at the peak, and nothing where the program steps
the state without the kernel."""

import pytest

from harness import spec

BENCH = spec.load_benchmark()
CFG = spec.config_of(BENCH, "granite_4_0_h_micro")


def _ctx(custom_calls):
    # ten decode steps of 64 rows, 62 active, in a 10 s window; the trace
    # is one second of it: one step traced
    return {"summary": {"bucket_util": {"decode@64": {
                "steps": 10, "rows": 640, "active_rows": 620,
                "wall_s": 0.4}}},
            "config": CFG, "window_s": 10.0,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"window_s": 1.0, "busy_s": 0.9,
                      "ops": dict(custom_calls, **{"fusion.1:f32[8]": 0.3}),
                      "custom_calls": custom_calls}}


def test_the_share_counts_the_active_rows_state_twice_a_layer():
    read = spec.reader_of("kernel.ssd_decode_roofline")
    calls = {"ssd_decode.3:f32[36,65,64,64,128]": 0.006,
             "ssd_decode:f32[36,65,64,64,128]": 0.004,
             "paged_attention.1:f32[64,8,4,128]": 0.5}
    # 36 layers x 62 rows x (64 x 64 x 128 float32) in and out, once
    need = 36 * 2 * 62 * 64 * 64 * 128 * 4
    assert read(_ctx(calls)) == pytest.approx(100 * need / (0.01 * 819e9))


@pytest.mark.parametrize("calls", [{}, {"custom-call.4:f32[8]": 0.01}])
def test_no_share_without_the_kernel(calls):
    read = spec.reader_of("kernel.ssd_decode_roofline")
    assert read(_ctx(calls)) is None
    assert read({}) is None
