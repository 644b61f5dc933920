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


def four_chip_trace(async_line=True):
    """Two supersteps on four chips, as a v5e host's profiler writes them:
    each chip's synchronous ops, with the permutes' done ops, and the
    permutes' start-to-done spans on the async line of chip 0 alone."""
    devices = {}
    for c in range(4):
        ops = [E(0, 300, "%fusion.1 = f32[8] fusion()"),
               E(300 + 10 * c, 20, "%collective-permute-done = u8[8] "
                 "collective-permute-done(%collective-permute-start)"),
               E(500, 300 - 50 * c, "%fusion.2 = f32[8] fusion()"),
               E(800 - 50 * c, 20, "%collective-permute-done.1 = u8[8] "
                 "collective-permute-done(%collective-permute-start.1)")]
        starts = [E(250, 70, "%collective-permute-start = (u8[8], u8[8]) "
                    "collective-permute-start(u8[8] %a)"),
                  E(700, 120, "%collective-permute-start.1 = (u8[8], u8[8]) "
                    "collective-permute-start(u8[8] %b)")]
        devices[f"/device:TPU:{c}"] = tr.DeviceLines(
            ops=ops, async_ops=starts if async_line and c == 0 else [])
    return tr.Trace(devices, [E(0, 1000, tr.WINDOW_SPAN)])


def _train_readings(trace):
    from bench.harness import Readings
    return Readings(None, {"readings": {"supersteps": 2}},
                    tr.summarize(trace), {})


def test_four_chip_idle_is_averaged_over_the_chips():
    from bench.harness import load_module
    r = _train_readings(four_chip_trace())
    # chip c is busy 300 + 20 + (300 - 50c) + 20 ns of the 1000
    busy = [640 - 50 * c for c in range(4)]
    assert r.trace.busy_s == pytest.approx(sum(busy) / 4 * 1e-9)
    assert load_module("metrics", "device_idle.train").read(r) == \
        pytest.approx(100 * (1 - sum(busy) / 4 / 1000))


@pytest.mark.parametrize("async_line", [True, False],
                         ids=["start_to_done", "done_ops"])
def test_four_chip_collective_ms_per_chip_and_superstep(async_line):
    from bench.harness import load_module
    r = _train_readings(four_chip_trace(async_line))
    # with the async line: chip 0's spans, 70 + 120 ns over 2 supersteps,
    # not divided by the three chips that have no such line; without it,
    # every chip's two 20 ns done ops
    want_ns = (70 + 120) / 2 if async_line else 2 * 20 / 2
    assert load_module("metrics", "collective_ms.train").read(r) == \
        pytest.approx(want_ns * 1e-6)


def test_ops_by_device_keeps_each_chip_apart():
    s = tr.summarize(four_chip_trace())
    per = s.ops_by_device(r"collective-permute(-done)?")
    assert [len(evs) for evs in per] == [2, 2, 2, 2]
    assert [len(evs) for evs in s.ops_by_device(
        r"collective-permute(-start)?", line="async_ops")] == [2, 0, 0, 0]
    assert len(s.ops(r"collective-permute(-done)?")) == 8
