"""The trace reducer: exact arithmetic on a trace written out by hand, then
the small trace recorded on a v5e that sits beside this file
(``tests/benchmarks/record_sample_trace.py`` made it)."""

import os

import numpy as np
import pytest

from benchmarks.lib import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
UNIT = 1e-5  # seconds to one unit of the hand-written trace's times
US = 10_000_000  # picoseconds to that unit

FUSION = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kLoop"
FLASH = ('%attn.3 = (bf16[2,4,512,64]{3,2,1,0}, f32[2,4,512,8]{3,2,1,0}) custom-call(bf16[2,4,512,64]{3,2,1,0} %q), '
         'custom_call_target="tpu_custom_call"')
WHILE = "%while.2 = (s32[], bf16[8,8]{1,0}) while((s32[], bf16[8,8]{1,0}) %t), condition=%c, body=%b"
AG_START = "%all-gather-start.1 = (bf16[8]{0}, bf16[32]{0}) all-gather-start(bf16[8]{0} %x), dimensions={0}"
AG_DONE = "%all-gather-done.1 = bf16[32]{0} all-gather-done((bf16[8]{0}, bf16[32]{0}) %all-gather-start.1)"
RS = "%reduce-scatter.4 = f32[8]{0} reduce-scatter(f32[32]{0} %g), dimensions={0}, to_apply=%add"
NAMES = [FUSION, FLASH, WHILE, AG_START, AG_DONE, RS, "jit_train_step(77)", "bench:window",
         "bench:train_batch"]


def event(name, start_us, dur_us):
    return "events { metadata_id: %d offset_ps: %d duration_ps: %d }" % (
        NAMES.index(name) + 1, start_us * US, dur_us * US)


def plane(name, lines):
    meta = "\n".join('event_metadata { key: %d value { id: %d name: "%s" } }' % (
        i + 1, i + 1, n.replace('"', '\\"')) for i, n in enumerate(NAMES))
    body = "\n".join('lines { name: "%s" timestamp_ns: 0 %s }' % (n, " ".join(ev)) for n, ev in lines)
    return 'planes { name: "%s" %s %s }' % (name, body, meta)


def device(n):
    # in units:  while 10..100 holds everything; fusion 10..40, flash 40..60,
    # all-gather in flight 20..70 (start at 20, done waits 60..70), idle 70..80,
    # reduce-scatter 80..90 (synchronous), fusion 90..100
    return plane("/device:TPU:%d" % n, [
        ("XLA Modules", [event("jit_train_step(77)", 10, 90)]),
        ("XLA Ops", [event(WHILE, 10, 90), event(FUSION, 10, 30), event(AG_START, 20, 0),
                     event(FLASH, 40, 20), event(AG_DONE, 60, 10), event(RS, 80, 10),
                     event(FUSION, 90, 10)]),
        ("Async XLA Ops", [event(AG_START, 20, 50)]),
    ])


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    host = plane("/host:CPU", [("main", [event("bench:window", 0, 110),
                                         event("bench:train_batch", 6, 98)])])
    return xplane.reduce_profile(ProfileData.from_text_proto(device(0) + device(1) + host))


def test_window_busy_and_idle(reduced):
    assert reduced.n_devices == 2
    assert reduced.window_s == pytest.approx(110 * UNIT)
    # leaves cover 10..70 and 80..100; the while that spans the gap is a container
    assert reduced.busy_s == pytest.approx(80 * UNIT)
    gaps = dict(reduced.idle_gaps)
    assert gaps["host, after start before train_batch"] == pytest.approx(10 * UNIT)  # 0..10, mid 5
    assert gaps["in train_batch"] == pytest.approx(10 * UNIT)  # 70..80
    assert gaps["host, after train_batch before end"] == pytest.approx(10 * UNIT)  # 100..110


def test_programs_and_kernels(reduced):
    assert reduced.modules == {"train_step": [pytest.approx(90 * UNIT)]}
    flash = lambda op: xplane.PALLAS_TARGET in op.text and "bf16[2,4,512,64]" in op.text  # noqa: E731
    assert reduced.op_seconds(flash) == pytest.approx(20 * UNIT)  # mean over the two devices
    assert reduced.op_count(flash) == 2
    assert all(op.module == "train_step" for op in reduced.ops)
    top = dict(reduced.top_ops())
    assert top["fusion.1 fusion bf16[8,8]"] == pytest.approx(40 * UNIT)
    assert top["attn.3 pallas bf16[2,4,512,64]"] == pytest.approx(20 * UNIT)
    assert not any("while" in name for name in top)


def test_collectives_and_their_exposed_part(reduced):
    # in flight 20..70, synchronous 80..90: 60 units; other work covers 20..60
    assert reduced.collective_s == pytest.approx(60 * UNIT)
    assert reduced.collective_exposed_s == pytest.approx(20 * UNIT)  # 60..70 waiting, 80..90
    assert set(reduced.collective_by_kind) == {"all-gather", "reduce-scatter"}
    assert reduced.collective_by_kind["reduce-scatter"] == pytest.approx(10 * UNIT)


@pytest.mark.parametrize("text,want", [
    (FUSION, ("fusion.1", "fusion", "bf16[8,8]")),
    (FLASH, ("attn.3", "custom-call", "bf16[2,4,512,64]")),
    (WHILE, ("while.2", "while", "s32[]")),
    ("%copy.2 = bf16[2,512,4,64]{3,1,2,0:T(8,128)(2,1)S(1)} copy(bf16[2,512,4,64]{1,3,2,0:T(8,128)(2,1)} %q.1)",
     ("copy.2", "copy", "bf16[2,512,4,64]")),
    ("jit_step(1)", ("jit_step(1)", "", "")),
])
def test_split_instruction(text, want):
    assert xplane.split_instruction(text) == want


@pytest.mark.parametrize("text,kind", [
    (AG_START, "all-gather"), (AG_DONE, "all-gather"), (RS, "reduce-scatter"), (FUSION, None),
    ("%all-reduce-start = ((f32[4]{0}), f32[4]{0}) async-start(f32[4]{0} %x), calls=%ar", "all-reduce"),
])
def test_collective_kind(text, kind):
    assert xplane.collective_kind(text) == kind


def test_interval_arithmetic():
    iv = np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [6.0, 6.5]])
    assert xplane.union(iv).tolist() == [[0.0, 3.0], [5.0, 7.0]]
    assert xplane.length(xplane.union(iv)) == 5.0
    left = xplane.subtract(np.array([[0.0, 10.0]]), xplane.union(iv))
    assert left.tolist() == [[3.0, 5.0], [7.0, 10.0]]
    assert xplane.clip(xplane.union(iv), 1.0, 6.0).tolist() == [[1.0, 3.0], [5.0, 6.0]]
    assert xplane.union(np.zeros((0, 2))).shape == (0, 2)


def test_the_trace_recorded_on_one_v5e_chip():
    r = xplane.reduce_trace(os.path.join(DATA, "v5e_1chip_sample.xplane.pb"))
    assert r.n_devices == 1 and list(r.modules) == ["sample_step"]
    assert len(r.modules["sample_step"]) == 3  # three traced calls, ~21.6 us each
    assert all(21e-6 < s < 22e-6 for s in r.modules["sample_step"])
    flash = lambda op: xplane.PALLAS_TARGET in op.text and "bf16[2,4,512,64]" in op.text  # noqa: E731
    assert r.op_count(flash) == 3
    assert r.op_seconds(flash) == pytest.approx(3 * 11.25e-6, rel=0.01)
    assert 0 < r.busy_s < 3 * 22e-6 < r.window_s
    assert r.collective_s == 0.0 and r.top_ops(1)[0][0].startswith("sample_step.1 pallas")


def test_a_trace_with_no_device_plane_is_refused():
    from jax.profiler import ProfileData

    host_only = ProfileData.from_text_proto(plane("/host:CPU", [("main", [event("bench:window", 0, 5)])]))
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce_profile(host_only)


def test_the_trace_recorded_on_four_v5e_chips():
    """sample_step over fsdp=4: a psum, an all-gather and a reduce-scatter,
    none overlapped by other work, beside the matmul and the flash kernel."""
    r = xplane.reduce_trace(os.path.join(DATA, "v5e_4chip_sample.xplane.pb"))
    assert r.n_devices == 4 and len(r.modules["sample_step"]) == 3
    assert {"all-reduce", "all-gather", "reduce-scatter"} <= set(r.collective_by_kind)
    assert r.collective_s == pytest.approx(sum(r.collective_by_kind.values()), rel=0.02)
    assert r.collective_exposed_s == pytest.approx(r.collective_s)  # nothing runs beside them
    assert 0.5 * r.busy_s < r.collective_s < r.busy_s < r.window_s
    flash = lambda op: xplane.PALLAS_TARGET in op.text and "bf16[2,4,512,64]" in op.text  # noqa: E731
    assert r.op_count(flash) == 3 * 4  # three calls on each of four chips
    assert r.op_seconds(flash) == pytest.approx(3 * 11.2e-6, rel=0.05)  # mean over the chips
