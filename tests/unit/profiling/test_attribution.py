"""Step-time attribution: the four buckets always sum exactly to the wall.

The decomposition never invents time — every estimate is clamped to what
remains of the measured wall, and the residual is an honest ``stall``
bucket. These tests pin the clamping order, the roofline bound verdicts,
the measured-source joins (program registry / coll hops / tracer spans)
and the published gauge surface.
"""

import pytest

from deepspeed_tpu.profiling.attribution import (
    PEAK_BYTES_PER_S,
    PEAK_FLOPS,
    attribute,
    attribute_program,
    measured_collective_s,
    span_last_s,
)
from deepspeed_tpu.telemetry.programs import ProgramRecord, get_program_registry
from deepspeed_tpu.telemetry.registry import MetricsRegistry


def _sum_ms(attr):
    return (attr.compute_ms + attr.collective_ms + attr.host_ms
            + attr.stall_ms)


def test_buckets_sum_exactly_to_wall():
    attr = attribute("step", 0.010, flops=2e9, bytes_accessed=5e7,
                     peak_flops=1e12, peak_bytes_per_s=50e9,
                     collective_s=0.001, host_s=0.0005, publish=False)
    # flop term 2ms > bw term 1ms -> compute=2ms; then coll 1ms, host 0.5ms
    assert attr.compute_ms == pytest.approx(2.0)
    assert attr.collective_ms == pytest.approx(1.0)
    assert attr.host_ms == pytest.approx(0.5)
    assert attr.stall_ms == pytest.approx(6.5)
    assert _sum_ms(attr) == pytest.approx(attr.wall_ms, rel=1e-9)
    assert attr.bound == "stall"
    assert attr.flops_fraction == pytest.approx(0.2)


def test_clamping_order_compute_then_coll_then_host():
    # estimates larger than the wall: compute soaks it all, the rest clamp
    # to zero, and the total still equals the wall exactly
    attr = attribute("step", 0.001, flops=1e12, bytes_accessed=0.0,
                     peak_flops=1e12, collective_s=5.0, host_s=5.0,
                     publish=False)
    assert attr.compute_ms == pytest.approx(1.0)
    assert attr.collective_ms == 0.0
    assert attr.host_ms == 0.0
    assert attr.stall_ms == 0.0
    assert attr.bound == "compute"


def test_memory_bound_verdict():
    # bw term (4ms) dominates flop term (1ms): compute-bucket-dominant but
    # the verdict names the roofline regime actually hit
    attr = attribute("step", 0.005, flops=1e9, bytes_accessed=200e6,
                     peak_flops=1e12, peak_bytes_per_s=50e9, publish=False)
    assert attr.bound == "memory"
    assert attr.compute_ms == pytest.approx(4.0)


def test_comm_and_host_bounds():
    comm = attribute("step", 0.010, collective_s=0.008, publish=False)
    assert comm.bound == "comm"
    host = attribute("step", 0.010, host_s=0.008, publish=False)
    assert host.bound == "host"


def test_zero_wall_and_missing_sources_are_safe():
    attr = attribute("step", 0.0, flops=1e9, peak_flops=1e12,
                     collective_s=1.0, publish=False)
    assert _sum_ms(attr) == 0.0
    assert attr.flops_fraction == 0.0
    rendered = attribute("step", 0.010, publish=False).render()
    assert "stall" in rendered


def test_publish_gauge_surface():
    reg = MetricsRegistry()
    attribute("train_step", 0.010, flops=2e9, peak_flops=1e12,
              registry=reg, publish=True)
    g = reg.gauges()
    assert g['perf/attribution_wall_ms{program="train_step"}'] == pytest.approx(10.0)
    assert g['perf/attribution_compute_ms{program="train_step"}'] == pytest.approx(2.0)
    assert g['perf/attribution_bound{bound="stall",program="train_step"}'] == 1.0
    assert g['perf/roofline_flops_fraction{program="train_step"}'] == pytest.approx(0.2)


# --------------------------------------------------------- measured joins
def test_measured_collective_sums_hop_probes():
    reg = MetricsRegistry()
    assert measured_collective_s(reg) == 0.0
    reg.histogram("coll/hop_ms", route="sig0").observe(2.0)
    reg.histogram("coll/hop_ms", route="sig1").observe(3.0)
    reg.histogram("coll/other_ms", route="sig0").observe(99.0)
    assert measured_collective_s(reg) == pytest.approx(0.005)


def test_span_last_s():
    reg = MetricsRegistry()
    assert span_last_s("data", reg) == 0.0  # never ran: honest zero
    reg.histogram("span/data").observe(7.5)
    assert span_last_s("data", reg) == pytest.approx(7.5)


def test_attribute_program_joins_program_registry():
    preg = get_program_registry()
    preg.reset()
    preg._records["fake_step"] = [ProgramRecord(
        label="fake_step", index=0, flops=2e9, bytes_accessed=5e7)]
    reg = MetricsRegistry()
    reg.histogram("coll/hop_ms", route="sig0").observe(1.0)  # ms
    reg.histogram("span/data").observe(0.0005)               # seconds
    try:
        attr = attribute_program("fake_step", 0.010, backend="cpu",
                                 registry=reg, publish=False)
    finally:
        preg.reset()
    # cpu peaks: flop term 2e9/1e12=2ms > bw term 5e7/50e9=1ms
    assert attr.compute_ms == pytest.approx(2.0)
    assert attr.collective_ms == pytest.approx(1.0)
    assert attr.host_ms == pytest.approx(0.5)
    assert _sum_ms(attr) == pytest.approx(attr.wall_ms, rel=1e-9)


def test_attribute_program_without_capture_is_all_stall():
    preg = get_program_registry()
    preg.reset()
    attr = attribute_program("never_captured", 0.010, backend="cpu",
                             registry=MetricsRegistry(), publish=False)
    assert attr.compute_ms == 0.0
    assert attr.stall_ms == pytest.approx(10.0)


def test_peak_envelopes_cover_all_ledger_backends():
    for backend in ("cpu", "tpu-v5-lite", "interpret"):
        assert PEAK_FLOPS[backend] > 0
        assert PEAK_BYTES_PER_S[backend] > 0
