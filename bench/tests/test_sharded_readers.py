"""The readers of the mesh-sharded solve cell, on hand-made traces of four
devices: the kernel's roofline per shard, the exposed collectives, and
the shares of the program's exchange and reduce scopes."""
import pytest

from bench import scopes
from bench import trace as T
from bench.common import Context
from bench.run import read_metric

SHARDED = {"grid": [32768, 32768], "shard": [16384, 16384],
           "kernel_fields": {"read": 2, "written": 1}}
READERS = ("sharded_roofline.solve", "exposed_exchange_share.solve",
           "halo_exchange_share.solve", "global_reduce_share.solve")


def _ctx(devices, window=100):
    tr = T.from_events(devices, [("bench.window", 0, window)])
    return Context(trace=tr, counters={"iters": [341]}, config=SHARDED,
                   peaks={"hbm_bytes_per_s": 819e9})


def test_sharded_roofline_by_hand():
    """Four chips, each sweeping its own 16384² shard: u and f read once
    and u' written once, 3 x 4 B x 16384², per call on each chip."""
    ms = 1_000_000
    devices = {f"/device:TPU:{d}": [
        ("stencil2d_fused_framed.3", 0, (4 + d) * ms),
        ("collective-permute-done.1", (4 + d) * ms, (5 + d) * ms),
        ("stencil2d_fused_framed.3", 10 * ms, (14 + d) * ms)]
        for d in range(4)}
    got = read_metric("sharded_roofline.solve", _ctx(devices, 20 * ms))
    per_call = 3 * 4 * 16384 * 16384
    assert per_call == 3_221_225_472
    kernel_s = sum(2 * (4 + d) for d in range(4)) * 1e-3     # 44 ms
    assert got == pytest.approx(100 * 8 * per_call / 819e9 / kernel_s)


def test_exposed_exchange_share_by_hand():
    """Chip 0: a permute [10,30] half under a fusion [20,40], an
    all-reduce [50,60] alone; chip 1: a permute [0,20] alone.  Exposed:
    10 + 10 on chip 0, 20 on chip 1, a mean of 20 in a window of 100."""
    devices = {"/device:TPU:0": [("collective-permute.1", 10, 30),
                                 ("fusion.2", 20, 40),
                                 ("all-reduce.3", 50, 60)],
               "/device:TPU:1": [("collective-permute.1", 0, 20),
                                 ("stencil2d_fused_framed.4", 20, 90)]}
    got = read_metric("exposed_exchange_share.solve", _ctx(devices))
    assert got == pytest.approx(20.0)


def test_exposed_exchange_share_is_zero_without_collectives():
    devices = {"/device:TPU:0": [("stencil2d_fused_framed.4", 0, 90)]}
    assert read_metric("exposed_exchange_share.solve",
                       _ctx(devices)) == 0.0


SCOPES = {"collective-permute-start.1": "repro.halo_exchange",
          "collective-permute-done.1": "repro.halo_exchange",
          "dynamic-update-slice.7": "repro.halo_exchange",
          "all-reduce.9": "repro.global_reduce",
          "select.4": "repro.done_mask"}


def test_scope_shares_by_hand(monkeypatch):
    """Each chip runs the same executable; the shares average the
    chips' time in each scope over the window."""
    monkeypatch.setattr(scopes, "solve_op_scopes", lambda: SCOPES)
    devices = {"/device:TPU:0": [("collective-permute-done.1", 0, 4),
                                 ("dynamic-update-slice.7", 4, 10),
                                 ("all-reduce.9", 10, 13),
                                 ("stencil2d_fused_framed.2", 13, 90)],
               "/device:TPU:1": [("collective-permute-done.1", 0, 2),
                                 ("dynamic-update-slice.7", 2, 6),
                                 ("all-reduce.9", 6, 7),
                                 ("select.4", 7, 9)]}
    ctx = _ctx(devices)
    # (10 + 6) / 2 and (3 + 1) / 2 in a window of 100
    assert read_metric("halo_exchange_share.solve", ctx) == \
        pytest.approx(8.0)
    assert read_metric("global_reduce_share.solve", ctx) == \
        pytest.approx(2.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_none_when_not_traced(name, monkeypatch):
    monkeypatch.setattr(scopes, "solve_op_scopes", lambda: SCOPES)
    ctx = Context(trace=None, counters={"iters": [341]}, config=SHARDED,
                  peaks=None)
    assert read_metric(name, ctx) is None


@pytest.mark.parametrize("names", [None, {"select.4": "repro.done_mask"}])
@pytest.mark.parametrize("name", ["halo_exchange_share.solve",
                                  "global_reduce_share.solve"])
def test_scope_readers_none_where_the_program_lacks_the_scope(
        name, names, monkeypatch):
    """A program without spans (no map), or one whose solve names no
    exchange or reduce (a solve on one chip, or a program from before
    the two scopes), gives no reading, where 0 would read as a gain."""
    monkeypatch.setattr(scopes, "solve_op_scopes", lambda: names)
    devices = {"/device:TPU:0": [("collective-permute-done.1", 0, 4),
                                 ("all-reduce.9", 10, 13)]}
    assert read_metric(name, _ctx(devices)) is None
