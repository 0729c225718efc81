"""The program's own names: host spans, device scopes and counters
(``repro.core.spans``), on CPU at tiny sizes."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FarmEngine, LoopOfStencilReduce, spans
from repro.kernels import ops


def _fields(n=64):
    u0 = jnp.zeros((n, n), jnp.float32)
    f = jax.random.normal(jax.random.key(0), (n, n), jnp.float32)
    return u0, f


@pytest.mark.parametrize("backend,unroll", [("pallas", 1),
                                            ("pallas-multistep", 2)])
def test_op_scopes_name_done_mask_and_ghost_refresh(backend, unroll):
    u0, f = _fields()
    compiled = ops.jacobi_solve.lower(
        u0, f, alpha=1.0, dx=1.0, tol=np.float32(1e-5), max_iters=40,
        backend=backend, unroll=unroll).compile()
    scopes = spans.op_scopes(compiled)
    assert "repro.done_mask" in scopes.values()
    assert "repro.ghost_refresh" in scopes.values()
    names = {m.group(1) for line in compiled.as_text().splitlines()
             if (m := spans._INSTR.match(line))}
    assert set(scopes) <= names


@pytest.mark.parametrize("backend,unroll", [("pallas", 1),
                                            ("pallas-multistep", 2)])
def test_one_device_scope_set_is_unchanged(backend, unroll):
    """A solve on one chip calls no collective: its executable names the
    done-mask and the ghost refresh, and neither mesh scope."""
    u0, f = _fields()
    compiled = ops.jacobi_solve.lower(
        u0, f, alpha=1.0, dx=1.0, tol=np.float32(1e-5), max_iters=40,
        backend=backend, unroll=unroll).compile()
    assert set(spans.op_scopes(compiled).values()) == {
        "repro.done_mask", "repro.ghost_refresh"}


SHARDED_SCOPES = """
import json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.core import GridPartition, spans
from repro.kernels import ops
from repro.sharding.specs import make_mesh

part = GridPartition(mesh=make_mesh((2, 2), ("rows", "cols")),
                     axis_names=("rows", "cols"), array_axes=(0, 1))
x = jax.ShapeDtypeStruct((128, 128), jnp.float32,
                         sharding=NamedSharding(part.mesh, part.pspec))
compiled = ops.jacobi_solve.lower(
    x, x, alpha=0.1, dx=1.0, tol=np.float32(1e-5), max_iters=2000,
    backend="pallas-sharded", unroll=1, part=part).compile()
scopes = spans.op_scopes(compiled)
ops_ = {}
for line in compiled.as_text().splitlines():
    name = spans._INSTR.match(line)
    kind = re.search(r"\\s(collective-permute|all-reduce)(-start|-done)?\\(",
                     line)
    if name and kind:
        ops_[name.group(1)] = kind.group(1)
print(json.dumps({"scopes": scopes, "ops": ops_}))
"""


def test_sharded_solve_scopes_its_collectives():
    """On a 2×2 mesh of CPU devices every collective-permute of the
    solve's executable is the halo exchange, and every all-reduce the
    mesh-wide reduce."""
    import json
    import subprocess
    import sys

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "..", "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED_SCOPES], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    scopes, kinds = got["scopes"], got["ops"]
    permutes = [n for n, k in kinds.items() if k == "collective-permute"]
    reduces = [n for n, k in kinds.items() if k == "all-reduce"]
    assert permutes and reduces
    assert {scopes.get(n) for n in permutes} == {"repro.halo_exchange"}
    assert {scopes.get(n) for n in reduces} == {"repro.global_reduce"}


def test_op_scopes_fusion_takes_its_root_scope():
    text = """HloModule m

%fused_computation (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %b = f32[8]{0} broadcast(%p0), metadata={op_name="jit(f)/repro.ghost_refresh/x"}
  ROOT %select.1 = f32[8]{0} select(%b, %p0, %p0), metadata={op_name="jit(f)/while/body/repro.ghost_refresh/repro.done_mask/jit(_where)/select_n"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %dynamic-update-slice.3 = f32[8]{0} dynamic-update-slice(%a, %a), metadata={op_name="jit(f)/repro.ghost_refresh/scatter"}
  %add.4 = f32[8]{0} add(%a, %a), metadata={op_name="jit(f)/add"}
  ROOT %broadcast_select_fusion.2 = f32[8]{0} fusion(%a, %a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/repro.ghost_refresh/x"}
}
"""
    scopes = spans.op_scopes(text)
    assert scopes["broadcast_select_fusion.2"] == "repro.done_mask"
    assert scopes["dynamic-update-slice.3"] == "repro.ghost_refresh"
    assert "add.4" not in scopes and "a" not in scopes


def test_traces_count_new_signatures_only():
    u0, f = _fields(32)
    kw = dict(alpha=0.75, dx=1.0, tol=np.float32(1e-3), max_iters=23,
              backend="jnp")
    before = spans.counts()
    ops.jacobi_solve(u0, f, **kw)
    mid = spans.counts()
    ops.jacobi_solve(u0, f, **kw)
    after = spans.counts()
    assert mid["traces"] - before["traces"] == 1
    assert after["traces"] == mid["traces"]
    assert after["compiles"] == mid["compiles"]


def test_entry_logs_the_calls_that_traced():
    entry = spans.Entry("unit", lambda x, *, k: x * k, static_argnames="k")
    x = jnp.ones((4,), jnp.float32)
    entry(x, k=2)
    entry(x, k=2)
    assert entry.after_first == {"traces": 0, "compiles": 0,
                                 "cache_loads": 0}
    assert not entry.mixed
    entry(x, k=3)                      # a new static value: a retrace
    assert entry.after_first["traces"] == 1
    assert entry.after_first["compiles"] + \
        entry.after_first["cache_loads"] == 1
    assert entry.calls == 3 and entry.mixed
    args, kwargs = entry.signature
    assert args[0] == jax.ShapeDtypeStruct((4,), jnp.float32,
                                           sharding=x.sharding)
    assert kwargs == {"k": 3}


def test_entry_signature_abstracts_traced_values_only():
    """A traced scalar that changes value (a tolerance) is the same
    signature; a static one is not."""
    entry = spans.Entry("unit", lambda x, t, *, k: x * k + t,
                        static_argnames=("k",))
    x = jnp.ones((4,), jnp.float32)
    entry(x, np.float32(np.inf), k=2)
    entry(x, np.float32(1e-5), k=2)
    entry(x + 1.0, np.float32(0.5), k=2)
    assert not entry.mixed
    assert entry.after_first["traces"] == 0
    args, _ = entry.signature
    assert args[1] == jax.ShapeDtypeStruct((), jnp.float32)


def test_entry_op_scopes_of_its_one_executable():
    entry = spans.Entry(
        "unit", lambda x, *, k: jnp.where(x > k, x, 0.0) + 1.0,
        static_argnames="k")
    assert entry.op_scopes() is None             # no call yet
    x = jnp.ones((8,), jnp.float32)
    entry(x, k=0.5)
    assert entry.op_scopes() == {}               # no repro.* scope in it
    entry(jnp.ones((9,), jnp.float32), k=0.5)    # a second executable
    assert entry.mixed and entry.op_scopes() is None


def test_entry_signature_tells_shardings_apart():
    """The same shape and dtype under another sharding is another
    executable."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding.specs import make_mesh

    entry = spans.Entry("unit", lambda x: x + 1.0)
    x = jnp.ones((8, 128), jnp.float32)
    entry(x)
    assert not entry.mixed
    mesh = make_mesh((1,), ("d",))
    entry(jax.device_put(x, NamedSharding(mesh, P("d"))))
    assert entry.mixed
    assert entry.signature[0][0].sharding.spec == P("d")


def test_compile_listener_tells_loads_from_compiles():
    before = spans.counts()
    # a persistent-cache hit reports its load inside the compile event
    spans._on_duration(spans.CACHE_LOAD_EVENT, 0.5)
    spans._on_duration(spans.COMPILE_EVENT, 0.6)
    spans._on_duration(spans.COMPILE_EVENT, 2.0)     # a real compile
    spans._on_duration("/jax/some/other/event", 9.0)
    after = spans.counts()
    assert after["cache_loads"] - before["cache_loads"] == 1
    assert after["compiles"] - before["compiles"] == 1


def _host_span_names(tdir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_solve_span_on_the_host_plane(tmp_path):
    u0, f = _fields(32)
    kw = dict(alpha=1.0, dx=1.0, tol=np.float32(1e-3), max_iters=20,
              backend="jnp")
    jax.block_until_ready(ops.jacobi_solve(u0, f, **kw))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(ops.jacobi_solve(u0, f, **kw))
    finally:
        jax.profiler.stop_trace()
    assert "repro.solve" in _host_span_names(str(tmp_path))


def countdown(get, *_):
    return get(0, 0) - 1.0


def test_farm_spans_on_the_host_plane(tmp_path):
    loop = LoopOfStencilReduce(
        f=countdown, k=1, combine="max", cond=lambda r: r < 0.5,
        boundary="zero", max_iters=32, backend="jnp", block=(32, 128))
    base = np.linspace(0.1, 0.9, 8 * 128, dtype=np.float32).reshape(8, 128)
    items = [base + float(t) for t in (2, 5, 3, 6, 1, 4)]
    eng = FarmEngine(loop, lanes=2, segment=4)
    got = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_continuous(items, got.append)
    finally:
        jax.profiler.stop_trace()
    assert sorted(r.index for r in got) == list(range(len(items)))
    names = _host_span_names(str(tmp_path))
    for name in ("prep", "stage", "dispatch", "drain", "check"):
        assert f"repro.farm.{name}" in names
