"""Domain bytes by hand, the peaks table, and the kernel-share readers."""
import pytest

from bench import roofline
from bench import trace as T
from bench.common import Context
from bench.run import read_metric


def test_helmholtz_16384_single_step_bytes():
    # u and f read once, u' written once: 3 x 4 B x 16384 x 16384
    assert roofline.domain_bytes((16384, 16384), read=2) == 3_221_225_472


def test_multistep_call_bytes_do_not_grow_with_t():
    # a T-sweep call still reads u, f and writes u' once
    assert roofline.domain_bytes((16384, 16384), read=2, written=1) == \
        3 * 4 * 16384 * 16384


def test_v5e_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peaks(kind)


def _ctx(events, config, counters=None):
    tr = T.from_events({"/device:TPU:0": events},
                       [("bench.window", 0, 10_000_000)])
    return Context(trace=tr, counters=counters or {}, config=config,
                   peaks={"hbm_bytes_per_s": 819e9})


HELM = {"grid": [16384, 16384], "kernel_fields": {"read": 2, "written": 1}}


def test_fused_roofline_by_hand():
    # two calls of 3.93 ms least time each, in 8 ms of kernel time
    ev = [("stencil2d_fused_framed.1", 0, 4_000_000),
          ("stencil2d_fused_framed.1", 5_000_000, 9_000_000)]
    got = read_metric("fused_roofline.solve", _ctx(ev, HELM))
    least = 2 * 3_221_225_472 / 819e9
    assert got == pytest.approx(100 * least / 8e-3)
    assert read_metric("multistep_roofline.solve", _ctx(ev, HELM)) is None


def test_shares_of_the_window():
    ev = [("stencil2d_fused_framed.1", 0, 6_000_000),
          ("fusion.2", 6_000_000, 7_000_000)]
    ctx = _ctx(ev, HELM)
    assert read_metric("idle_share.solve", ctx) == pytest.approx(30.0)
    assert read_metric("outside_kernel_share.solve", ctx) == \
        pytest.approx(10.0)


def test_counter_readers():
    ctx = Context(trace=None, config={}, peaks=None,
                  counters={"iters": [340, 342]})
    assert read_metric("solve_iters", ctx) == 341
    assert read_metric("idle_share.solve", ctx) is None
