"""Stream tier (generic) regressions: the sharded_farm jit wrapper must
be built once, not per call (a fresh ``jax.jit`` wrapper per ``run`` call
carries a fresh compilation cache — every batch retraced and recompiled
the worker), and the StreamRunner must unstack results LAZILY (the sink
consumes item i before item i+1 is sliced) and survive empty sources and
ragged final batches.  The engine tier (FarmEngine) is covered in
tests/core/test_farm.py."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import StreamRunner, farm, ofarm, pipe, sharded_farm


def test_sharded_farm_traces_once():
    from repro.sharding.specs import make_mesh
    mesh = make_mesh((1,), ("data",))
    traces = {"n": 0}

    def worker(x):
        traces["n"] += 1
        return x * 2.0

    run = sharded_farm(worker, mesh)
    batch = jnp.arange(8.0).reshape(8, 1)
    out1 = run(batch)
    out2 = run(batch)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(batch) * 2)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(batch) * 2)
    assert traces["n"] == 1, f"worker retraced {traces['n']}x"


def test_sharded_farm_new_shape_retraces_same_wrapper():
    from repro.sharding.specs import make_mesh
    mesh = make_mesh((1,), ("data",))
    traces = {"n": 0}

    def worker(x):
        traces["n"] += 1
        return x + 1.0

    run = sharded_farm(worker, mesh)
    run(jnp.zeros((4, 2)))
    run(jnp.zeros((4, 2)))          # cache hit
    run(jnp.zeros((8, 2)))          # new shape: one more trace
    assert traces["n"] == 2


def test_farm_of_pipe_still_composes():
    stage = pipe(lambda x: x + 1.0, lambda x: x * 3.0)
    out = farm(stage)(jnp.ones((4, 2)))
    np.testing.assert_allclose(np.asarray(out), np.full((4, 2), 6.0))
    out = ofarm(stage)(jnp.ones((4, 2)))
    np.testing.assert_allclose(np.asarray(out), np.full((4, 2), 6.0))


def test_stream_runner_empty_source():
    sunk = []
    n = StreamRunner(worker=jax.jit(lambda x: x), source=lambda: iter([]),
                     sink=sunk.append, batch=4).run()
    assert n == 0 and sunk == []


def test_stream_runner_ragged_final_batch():
    """5 items through batch=2: two full batches + a final batch of 1 —
    every item must reach the sink exactly once, in order."""
    items = [np.full((3,), float(i), np.float32) for i in range(5)]
    sunk = []
    n = StreamRunner(worker=jax.jit(lambda x: x * 2.0),
                     source=lambda: iter(items),
                     sink=sunk.append, batch=2).run()
    assert n == 5
    for i, out in enumerate(sunk):
        np.testing.assert_allclose(np.asarray(out), 2.0 * i)


def test_stream_runner_unstack_is_lazy():
    """The sink must see item i before item i+1 is sliced — _unstack is
    a generator, not a list of pre-materialised slices."""
    seen_at_slice = []

    class Probe:
        """Tree leaf that records how many sinks ran before each
        __getitem__ (lazy => strictly increasing prefix counts)."""
        shape = (3,)

        def __init__(self):
            self.log = seen_at_slice

        def __getitem__(self, i):
            self.log.append(("slice", i))
            return i

    gen = StreamRunner._unstack((Probe(),))
    first = next(gen)
    seen_at_slice.append(("sink", 0))
    second = next(gen)
    assert seen_at_slice == [("slice", 0), ("sink", 0), ("slice", 1)]
    assert (first, second) == ((0,), (1,))
