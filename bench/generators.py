"""Traffic generators: every input of a run, made from ``--seed``.

A traffic file (``bench/traffic/<mix>.json``) names one generator here
and gives its parameters.  The same seed gives the same inputs, and two
seeds give the same work in another form: the fields of the window are
one draw fixed by the traffic file, and the seed picks exact symmetries
of them.  A flip of the rows swaps each cell's north and south
neighbours, whose sum commutes exactly in floating point, and a change
of sign commutes with every operation of the Jacobi sweep.  So every
iteration count, and with it the time of a run, is the same for every
seed, while the inputs and answers differ.  After the window the same generator, given :func:`seed_draw`
in place of the traffic's fixed draw, makes inputs drawn from the seed
itself, which the check runs through the same program.
"""
from __future__ import annotations

import numpy as np


def seed_key(seed: int, *salt: int):
    """A JAX key from a seed of any size (seeds may pass 2**31)
    and optional salts, through numpy's SeedSequence."""
    import jax

    words = np.random.SeedSequence([int(seed), *map(int, salt)]
                                   ).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def seed_draw(seed: int) -> int:
    """A draw of the seed's own, in place of the traffic file's fixed one."""
    return int(np.random.SeedSequence([int(seed), 4]).generate_state(1)[0])


def _symmetry(seed: int):
    """The seed's exact symmetry: (flip the rows?, sign)."""
    bits = int(np.random.SeedSequence([int(seed), 3]).generate_state(1)[0])
    return bool(bits & 1), -1.0 if bits & 2 else 1.0


def forcing_fields(seed: int, shape, count: int, field_seed: int,
                   sharding=None):
    """``count`` N(0, 1) float32 forcing fields of ``shape`` drawn from
    ``field_seed``, each with the rows flipped and the sign changed as the
    seed says; made on the device in one jitted call (sharded over the
    mesh when ``sharding`` is given, never whole on one chip)."""
    import jax
    import jax.numpy as jnp

    def make(key, flip, sign):
        keys = jax.random.split(key, count)
        fields = (jax.random.normal(k, tuple(shape), jnp.float32)
                  for k in keys)
        return tuple(sign * jnp.where(flip, jnp.flip(f, 0), f)
                     for f in fields)

    flip, sign = _symmetry(seed)
    out = None if sharding is None else (sharding,) * count
    return list(jax.jit(make, out_shardings=out)(
        seed_key(field_seed), flip, np.float32(sign)))


def _row_flips(seed: int, count: int):
    """One bit per frame from the seed: flip that frame's rows?"""
    words = np.random.SeedSequence([int(seed), 5]).generate_state(count)
    return (words & 1).astype(bool)


def restoration_pool(seed: int, shape, count: int, pool_seed: int,
                     level: float):
    """``count`` float32 frames of ``shape`` drawn from ``pool_seed``: a
    smooth textured scene with ``level`` of its pixels replaced by 0 or 1,
    equally likely (salt-and-pepper noise, arXiv:1609.04567 sec. 4.3),
    each frame with its rows flipped where the seed says.  A row flip
    swaps each pixel's north and south neighbours, which the adaptive
    median (a sort) and the regularisation sweep (``a + b + c + d`` with
    ``a``, ``b`` the north and south ones) take exactly, so every seed
    does the same work.  Made on the device in one jitted call; returned
    as host (numpy) frames, since a stream's frames arrive from the host."""
    import jax
    import jax.numpy as jnp

    def make(key, flips):
        h, w = shape
        yy, xx = jnp.mgrid[0:h, 0:w].astype(jnp.float32)
        base = (0.5 + 0.3 * jnp.sin(xx / 25.0) * jnp.cos(yy / 18.0)
                + 0.2 * ((xx // 40 + yy // 30) % 2))
        base = jnp.clip(base, 0.0, 1.0)
        k1, k2 = jax.random.split(key)
        hit = jax.random.uniform(k1, (count, h, w)) < level
        salt = jax.random.uniform(k2, (count, h, w)) < 0.5
        frames = jnp.where(hit, salt.astype(jnp.float32), base)
        return jnp.where(flips[:, None, None], frames[:, ::-1], frames)

    frames = np.asarray(jax.jit(make)(seed_key(pool_seed),
                                      _row_flips(seed, count)))
    return [frames[i] for i in range(count)]
