"""The trace reduction and the per-layer readers, on hand-built traces.

The traces are XSpace text protos shaped like a TPU profile: a device
plane ``/device:TPU:0`` with ``XLA Modules`` and ``XLA Ops`` lines, and a
host plane whose thread line carries the benchmark's window annotation.
"""

import pytest
from jax.profiler import ProfileData

from bench import harness, loadgen, roofline, trace_reduce
from bench.peaks import peaks_for

US = 1_000_000  # picoseconds per microsecond


def esc(s):
    return s.replace("\\", "\\\\").replace('"', '\\"')


def kernel(name, n):
    """An op event named as a TPU trace names a Pallas call."""
    return (f"%{name}.{n} = f32[8,184]{{1,0:T(8,128)}} custom-call(s32[80] "
            f'%p.1), custom_call_target="tpu_custom_call"')


def fusion(n):
    return f"%fusion.{n} = f32[8,16384]{{1,0:T(8,128)}} fusion(f32[8] %x.2)"


def _plane(pid, name, lines, names):
    meta = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{esc(n)}" }} }}'
        for i, n in enumerate(names, 1))
    body = []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = "\n".join(
            f"events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {int(a * US)} duration_ps: {int((b - a) * US)} }}"
            for n, a, b in events)
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                    f"{evs} }}")
    return (f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body)
            + f"\n{meta} }}")


def make_trace(ops, modules=(), host=()):
    """Times in microseconds; the window annotation spans [0, 100]."""
    host = [(trace_reduce.WINDOW, 0, 100)] + list(host)
    dev_names = sorted({n for n, _, _ in list(ops) + list(modules)})
    host_names = sorted({n for n, _, _ in host})
    txt = (_plane(1, "/device:TPU:0", [("XLA Modules", list(modules)),
                                       ("XLA Ops", list(ops))], dev_names)
           + "\n" + _plane(2, "/host:CPU", [("python", host)], host_names))
    return trace_reduce.from_profile(ProfileData.from_text_proto(txt))


PROBE, STEP, FUSION = kernel("ivf_probe_scores", 3), kernel("mwem_step", 7), \
    fusion(1)
LOOP = "%while.145 = (s32[]) while(s32[] %t), body=%region_4"
WAVE_OPS = [(LOOP, 10, 34), (FUSION, 10, 14), (PROBE, 14, 20),
            (STEP, 20, 22), (FUSION, 22, 26), (PROBE, 26, 32), (STEP, 32, 34)]


def test_window_comes_from_the_host_annotation():
    tr = make_trace(WAVE_OPS)
    assert tr.window == pytest.approx((0.0, 100e-6))
    assert tr.window_s == pytest.approx(100e-6)


def test_busy_union_merges_overlaps_and_clips_to_the_window():
    tr = make_trace([("a", -5, 10), ("b", 5, 20), ("c", 15, 30),
                     ("d", 90, 120)])
    # [0, 30] and [90, 100] inside the window
    assert trace_reduce.busy_seconds(tr) == pytest.approx(40e-6)


def test_idle_gaps_longest_first_and_named_by_host_activity():
    tr = make_trace([("a", 10, 20), ("b", 70, 80)],
                    host=[("bench/submit", 20, 68), ("bench/answer", 85, 88)])
    gaps = trace_reduce.idle_gaps(tr)
    assert [(round(a * 1e6), round(b * 1e6)) for a, b in gaps] == \
        [(20, 70), (80, 100), (0, 10)]
    assert trace_reduce.host_activity(tr, *gaps[0]) == "bench/submit"
    assert trace_reduce.host_activity(tr, *gaps[1]) == "bench/answer"
    assert trace_reduce.host_activity(tr, *gaps[2]) == "host idle"


def test_kernel_time_counts_whole_calls_of_that_kernel():
    tr = make_trace(WAVE_OPS + [(STEP, 95, 105)])
    ops = trace_reduce.kernel_ops(tr, "ivf_probe_scores")
    assert len(ops) == 2
    assert sum(o.dur for o in ops) == pytest.approx(12e-6)
    assert len(trace_reduce.kernel_ops(tr, "mwem_step")) == 2  # one cut off


def _ctx(tr, T=2, ladder=(8,), spans=()):
    cfg = {"T": T, "U": 16384, "index": {"nprobe": 10, "cap": 182}}
    rec = loadgen.Record(window=(0.0, 1.0), spans=list(spans))
    return harness.Context(cfg=cfg, mix={"ladder": list(ladder)},
                           peaks=peaks_for("TPU v5 lite"), record=rec,
                           trace=tr)


def test_wave_readers_split_module_time_at_the_custom_calls():
    tr = make_trace(WAVE_OPS, modules=[("jit_core(1)", 10, 34),
                                       ("jit_other(2)", 40, 50)])
    ctx = _ctx(tr)
    # one whole wave of T = 2 iterations, 24 us long
    assert harness.reader("wave_iter_ms")(ctx) == pytest.approx(12e-3)
    # outside the kernels: 24 − (6 + 2 + 6 + 2) = 8 us over 2 iterations
    assert harness.reader("wave_xla_ms")(ctx) == pytest.approx(4e-3)
    assert harness.reader("device_idle_share")(ctx) == pytest.approx(76.0)


def test_readers_return_nothing_when_the_trace_holds_nothing():
    tr = make_trace([(FUSION, 10, 14)])
    ctx = _ctx(tr)
    for name in ("wave_iter_ms", "wave_xla_ms", "ivf_probe_scores_roofline",
                 "mwem_step_roofline"):
        assert harness.reader(name)(ctx) is None


def test_roofline_shares_are_least_time_over_kernel_time():
    tr = make_trace(WAVE_OPS)
    ctx = _ctx(tr)
    peaks = ctx.peaks
    f, b = roofline.ivf_probe_work(8, 10, 182, 16384)
    least, bound = roofline.least_seconds(f, b, peaks)
    assert bound == "bytes"
    got = harness.reader("ivf_probe_scores_roofline")(ctx)
    assert got == pytest.approx(100 * 2 * least / 12e-6)
    f, b = roofline.mwem_step_work(8, 16384)
    least, _ = roofline.least_seconds(f, b, peaks)
    assert harness.reader("mwem_step_roofline")(ctx) == \
        pytest.approx(100 * 2 * least / 4e-6)
    # a mix of wave sizes leaves the per-call lane count unknown
    assert harness.reader("mwem_step_roofline")(_ctx(tr, ladder=(2, 8))) \
        is None


def test_pump_p99_reads_the_release_path_spans_in_the_window():
    spans = [("submit", 0.1, 0.1 + 0.001 * i) for i in range(100)]
    spans += [("answer", 0.2, 5.0), ("pump", 2.0, 9.0)]   # read, outside
    got = harness.reader("pump_p99_ms")(_ctx(None, spans=spans))
    assert got == pytest.approx(1e3 * loadgen.quantile(
        [0.001 * i for i in range(100)], 0.99))


def test_top_ops_sum_leaves_per_instruction():
    tr = make_trace(WAVE_OPS)
    top = trace_reduce.top_ops(tr, 3)
    # the while loop holds the others, so it is not counted again
    assert [trace_reduce.instruction(n) for n, _ in top] == \
        ["ivf_probe_scores.3", "fusion.1", "mwem_step.7"]
    assert top[0][1] == pytest.approx(12e-6)
    assert "{" not in top[0][0]


def test_instruction_names_of_hlo_op_text():
    assert trace_reduce.instruction(PROBE) == "ivf_probe_scores.3"
    assert trace_reduce.base(PROBE) == "ivf_probe_scores"
    assert trace_reduce.base("%copy-done.5 = f32[8]{0} copy-done(%c)") == \
        "copy-done"
    assert trace_reduce.is_custom_call(trace_reduce.Event(PROBE, 0, 1))
    assert not trace_reduce.is_custom_call(trace_reduce.Event(FUSION, 0, 1))
