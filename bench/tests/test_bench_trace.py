"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on a small synthetic trace."""
import pytest

import _paths  # noqa: F401
from bench import trace_reduce as tr

DEV = "/device:TPU:0"


def _trace():
    ops = [(100, 200, "a"), (150, 300, "b"), (500, 600, "c"), (900, 1200, "d")]
    modules = [(100, 300, "jit_fused_intersect_compact_pairs(123)"),
               (500, 600, "jit__cooc_block(9)"),
               (900, 1200, "jit_fused_intersect_compact_pairs(77)")]
    return tr.Trace(ops={DEV: ops}, modules={DEV: modules},
                    host=[(0, 1000, "bench.window")])


def test_program_name_strips_prefix_and_fingerprint():
    assert tr.program_name("jit_fused_intersect_compact_pairs(1234)") == \
        "fused_intersect_compact_pairs"
    assert tr.program_name("jit__cooc_block(5)") == "_cooc_block"
    assert tr.program_name("custom") == "custom"


def test_union_length_merges_and_clips():
    assert tr.union_length([(100, 200), (150, 300), (500, 600)], 0, 1000) == 300
    assert tr.union_length([(100, 200), (150, 300)], 180, 250) == 70
    assert tr.union_length([], 0, 10) == 0


def test_reduce_busy_window_and_kernel_time():
    out = tr.reduce(_trace(), (0, 1000))
    # ops union inside [0, 1000): [100, 300) + [500, 600) + [900, 1000)
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    # a program counts when it lies inside the window
    assert out["module_s"] == {"fused_intersect_compact_pairs": 200e-9,
                               "_cooc_block": 100e-9}
    assert out["device_ops"][0] == ["fused_intersect_compact_pairs", 200e-9]


def test_idle_gaps_split_by_host_label():
    labels = [(0, 400, "mine:vertical"), (400, 700, "mine:bottom_up")]
    out = tr.reduce(_trace(), (0, 1000), labels)
    gaps = dict(out["idle_gaps"])
    # idle: [0,100) [300,500) [600,900); labels cover up to 700
    assert gaps == {"mine:vertical": pytest.approx(200e-9),
                    "mine:bottom_up": pytest.approx(200e-9),
                    "between": pytest.approx(200e-9)}
    assert sum(gaps.values()) == pytest.approx(1000e-9 - out["busy_s"])


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(RuntimeError):
        tr.reduce(tr.Trace(ops={}, modules={}, host=[]), (0, 10))
