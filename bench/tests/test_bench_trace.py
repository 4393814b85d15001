"""The reduction from a profiler trace to busy time, idle share,
executable time and idle gaps, on a synthetic trace."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401  (puts the harness on the path)
from harness import trace


def _trace():
    return trace.Trace(
        ops=[(0, 10, "fusion.a"), (5, 20, "fusion.b"), (30, 40, "fusion.a"),
             (0, 20, "%while.3 = (s32[]) while((s32[]) %t), body=%b"),
             (60, 70, "outside")],
        modules=[(0, 20, "jit_generate"), (30, 40, "jit_quantize_params")],
        spans=[(0, 50, trace.WINDOW_SPAN), (0, 25, "generate"),
               (25, 35, "sync"), (0, 60, "train_step")])


def test_union_merges_overlaps_and_clips_to_the_window():
    assert trace.union_ns(_trace().ops, 0, 50) == 30
    assert trace.union_ns(_trace().ops, 8, 35) == 17


def test_gaps_are_the_uncovered_stretches():
    assert trace.gaps_ns(_trace().ops, 0, 50) == [(20, 30), (40, 50)]


def test_reduce_reads_busy_idle_executables_and_labelled_gaps():
    r = trace.reduce(_trace())
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["executables_s"] == pytest.approx(
        {"jit_generate": 20e-9, "jit_quantize_params": 10e-9})
    assert r["device_ops"] == [["fusion.a", pytest.approx(20e-9)],
                               ["fusion.b", pytest.approx(15e-9)]]
    # the gap at 20-30 has its midpoint in `sync`, the innermost span;
    # the one at 40-50 in `train_step` alone
    assert r["idle_gaps"] == [["sync", pytest.approx(10e-9)],
                              ["train_step", pytest.approx(10e-9)]]


def test_reduce_finds_nothing_without_a_window_or_device_ops():
    t = _trace()
    assert trace.reduce(trace.Trace(ops=t.ops, modules=[], spans=[])) is None
    assert trace.reduce(trace.Trace(ops=[], modules=[], spans=t.spans)) \
        is None


def test_busy_is_averaged_over_devices():
    t = _trace()
    t.n_devices = 2
    assert trace.reduce(t)["busy_s"] == pytest.approx(15e-9)


def test_op_names_keep_the_instruction_and_its_type():
    assert trace.op_name("%copy.469 = f32[512,8]{1,0:T(8,128)} copy(f32[512,8]"
                         "{0,1} %x)") == "copy.469 f32[512,8]"
