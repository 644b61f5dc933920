"""The trace reduction on a hand-built trace: busy union, idle share,
device time per name, and idle gaps labelled by the host span open."""
import pytest

from bench import trace as tr

E = tr.Event
KERNEL = ('%quantize_mod.3 = (u8[8,256]{1,0}, f32[8,1]{1,0}) custom-call('
          'f32[8,256]{1,0} %a, f32[8,256]{1,0} %b), '
          'custom_call_target="tpu_custom_call"')


def two_chip_trace():
    chip0 = tr.DeviceLines(
        ops=[E(100, 50, "%fusion.1 = f32[8] fusion()"),
             E(140, 30, KERNEL),                       # overlaps fusion.1
             E(300, 100, "%fusion.2 = f32[8] fusion()"),
             E(310, 20, "%while.4 = (f32[8]) while()"),
             E(700, 10, "%fusion.3 = f32[8] fusion()")],   # after window
        async_ops=[E(120, 200, "%collective-permute-start.1 = (f32[8])")],
        modules=[E(90, 320, "jit_step(123)")])
    chip1 = tr.DeviceLines(ops=[E(60, 440, "%fusion.9 = f32[8] fusion()")],
                           modules=[E(60, 440, "jit_step(123)")])
    spans = [E(50, 500, tr.WINDOW_SPAN), E(170, 100, "train.feed"),
             E(250, 60, "train.dispatch"), E(440, 100, "train.block")]
    return tr.Trace({"/device:TPU:0": chip0, "/device:TPU:1": chip1}, spans)


def test_window_is_the_benchmark_span():
    s = tr.summarize(two_chip_trace())
    assert (s.lo, s.hi) == (50, 550)
    assert s.window_s == pytest.approx(500e-9)


def test_busy_is_the_union_averaged_over_chips():
    s = tr.summarize(two_chip_trace())
    # chip 0: [100,170] + [300,400] = 170 ns (fusion.3 is outside);
    # chip 1: [60,500] = 440 ns
    assert s.busy_s == pytest.approx((170 + 440) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(1 - 305 / 500)


def test_merge_clips_and_joins():
    assert tr.merge([(0, 10), (5, 20), (30, 40), (45, 99)], 2, 50) == \
        [(2, 20), (30, 40), (45, 50)]


def test_idle_gaps_longest_first_with_host_labels():
    s = tr.summarize(two_chip_trace())
    gaps = tr.idle_gaps(s.trace.devices["/device:TPU:0"].ops, s.lo, s.hi)
    assert gaps == [(400, 550), (170, 300), (50, 100)]
    spans = s.trace.spans
    assert tr.label_gap((400, 550), spans) == "train.block"
    assert tr.label_gap((170, 300), spans) == "train.feed"   # 100 vs 50 ns
    assert tr.label_gap((50, 100), spans) == "none"


def test_time_by_name_and_op_matching():
    s = tr.summarize(two_chip_trace())
    t = tr.time_by_name(s.trace.devices["/device:TPU:0"].ops, s.lo, s.hi)
    assert t == {"fusion": 150, "quantize_mod": 30, "while": 20}
    assert [e.dur_ns for e in s.ops("quantize_mod")] == [30]
    assert [e.dur_ns for e in s.ops("jit_step", line="modules")] == [320, 440]
    assert len(s.ops(r"collective-permute(-start)?", line="async_ops")) == 1


def test_breakdown_leaves_out_containers():
    b = tr.summarize(two_chip_trace()).breakdown()
    names = [k for k, _ in b["device_ops"]]
    assert names == ["fusion", "quantize_mod"] and "while" not in names
    assert b["device_ops"][0][1] == pytest.approx((150 + 440) / 2 * 1e-9)
    assert b["idle_gaps"][0] == ["train.block", pytest.approx(150e-9)]
    assert len(b["idle_gaps"]) <= 10


def test_op_names():
    assert tr.op_name(KERNEL) == "quantize_mod.3"
    assert tr.base_name(KERNEL) == "quantize_mod"
    assert tr.base_name("jit_decode_masked(1234)") == "jit_decode_masked"
