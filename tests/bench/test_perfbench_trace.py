"""The trace reduction and the roofline yardstick, on a trace recorded on
one v5e (``benchmark/testdata/v5e-codec.xplane.pb``: a Pallas encode of
``[8, 10, 1 MiB]``, a Pallas decode of ``[2, 10, 1 MiB]`` to 3 rows, an
einsum encode of ``[1, 10, 629146]`` and a Pallas d8p3 encode of
``[5, 8, 512 KiB]``)."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

from benchutil import BENCH

sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402
import work  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "v5e-codec.xplane.pb")
V5E = "TPU v5 lite"

#: (B, k, r, S) -> XLA Modules duration (ns) in the recorded trace
CALLS = {(8, 10, 4, 1 << 20): 1620089, (2, 10, 3, 1 << 20): 347269,
         (1, 10, 4, 629146): 113342, (5, 8, 3, 1 << 19): 243880}


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE, V5E, 1)


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(0.373954798, rel=1e-6)
    chip = summary.chips[0]
    assert chip.name == "/device:TPU:0"
    # the union of XLA Ops: each module's ops run back to back
    assert chip.busy_s == pytest.approx(0.002324543, rel=1e-6)
    assert chip.collective_s == 0.0


def test_codec_programs_found_by_shape(summary):
    chip = summary.chips[0]
    assert chip.shapes == {shape: 1 for shape in CALLS}
    # the union of the codec modules' own ops: here they fill each span
    assert chip.codec_s == pytest.approx(0.002324542, rel=1e-6)
    assert chip.codec_s <= chip.busy_s
    assert chip.codec_s == pytest.approx(sum(CALLS.values()) * 1e-9,
                                         rel=1e-4)
    least = 0.0
    for (b, k, r, s) in CALLS:
        ops, nbytes = 128 * k * r * b * s, (k + r) * b * s
        least += max(ops / 393e12, nbytes / 819e9)
    assert chip.codec_least_s == pytest.approx(least)
    assert chip.bounds == {"hbm": 4}
    share = chip.codec_least_s / chip.codec_s
    assert 0.05 < share < 0.15


def test_device_ops_and_idle_gaps(summary):
    ops = dict(summary.device_ops())
    assert ops["jit_call/call"] == pytest.approx(
        (1284851 + 347265 + 243876) * 1e-9)
    assert ops["jit_call/copy"] == pytest.approx(335221e-9)
    gaps = summary.idle_gaps
    assert len(gaps) == 10
    assert gaps[0][1] == max(g[1] for g in gaps)
    idle = sum(g[1] for g in gaps)
    assert idle <= summary.window_s - summary.chips[0].busy_s + 1e-9


def test_codec_shape_needs_matrix_and_shards():
    pallas = ["%call.1 = u8[2,3,1048576]{2,1,0} custom-call(s8[24,80]{1,0} "
              "%m2.1, u8[2,10,1048576]{2,1,0} %data.1)"]
    assert trace_reduce.codec_shape(pallas) == (2, 10, 3, 1048576)
    tp = ["%acc = s16[4,48,65536] custom-call(s8[48,40] %m, "
          "u8[4,5,65536] %x)", "%all-reduce = s16[4,48,65536] "
          "all-reduce(s16[4,48,65536] %acc)"]
    assert trace_reduce.codec_shape(tp) == (4, 5, 6, 65536)
    assert trace_reduce.codec_shape(["%add = f32[8,128] add(f32[8,128] %a,"
                                     " f32[8,128] %b)"]) is None
    assert trace_reduce.COLLECTIVE.search(tp[1])


def test_work_and_peaks():
    ops, nbytes = work.gf_apply_work(8, 10, 4, 1 << 20)
    assert ops == 128 * 40 * 8 * (1 << 20)
    assert nbytes == 14 * 8 * (1 << 20)
    peak = work.peaks(V5E)
    # d10p4: 366 operations per byte, under the chip's 480: HBM binds;
    # d20p6: 591 per byte, over it: the int8 units bind
    assert work.least_seconds(ops, nbytes, peak)[1] == "hbm"
    assert work.least_seconds(*work.gf_apply_work(4, 20, 6, 1 << 22),
                              peak)[1] == "int8"
    with pytest.raises(KeyError):
        work.peaks("TPU v4")


def _plane(modules, ops):
    ev = [NS(start_ns=lo, duration_ns=hi - lo, name=name)
          for lo, hi, name in ops]
    mods = [NS(start_ns=lo, duration_ns=hi - lo, name=name)
            for lo, hi, name in modules]
    return NS(name="/device:TPU:0",
              lines=[NS(name="XLA Ops", events=ev),
                     NS(name="XLA Modules", events=mods)])


@pytest.mark.parametrize("wait_ns", [0, 80_000])
def test_codec_time_is_its_ops_not_the_module_span(wait_ns):
    """A mesh chip's module span holds its wait for the other chips; the
    codec's device time is the union of its own ops, so a long wait
    leaves the share unchanged and it never exceeds busy."""
    call = ("%call.1 = u8[4,6,1048576]{2,1,0} custom-call(s8[48,160]{1,0}"
            " %m, u8[4,20,1048576]{2,1,0} %x)")
    ops = [(1_000, 3_000, "%copy.1 = u8[4,20,1048576]{2,1,0} copy(%p)"),
           (2_500, 11_000, call),
           (200_000, 201_000, "%add.2 = f32[8,128] add(%a, %b)")]
    module = [(500, 11_500 + wait_ns, "jit_fn(1)"),
              (199_500, 201_500, "jit_other(2)")]
    chip = trace_reduce._reduce_chip(_plane(module, ops),
                                     work.peaks(V5E))
    assert chip.busy_s == pytest.approx(11_000e-9)
    assert chip.codec_s == pytest.approx(10_000e-9)
    assert chip.codec_s <= chip.busy_s
    assert chip.shapes == {(4, 20, 6, 1 << 20): 1}
    least = work.least_seconds(*work.gf_apply_work(4, 20, 6, 1 << 20),
                               work.peaks(V5E))[0]
    assert chip.codec_least_s == pytest.approx(least)
