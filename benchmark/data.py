"""Seeded inputs, made on the device by the benchmark's own generator.

Nothing here imports the program: the links and sources a run solves
are the yardstick's, so a later change to ``quda_tpu``'s random fields
cannot change the work.  Every seed solves the SAME physical gauge
configuration (the traffic file's ``gauge_seed``) in another gauge: the
links are rotated by a random SU(3) field g(x) drawn from ``--seed``,
U'_mu(x) = g(x) U_mu(x) g(x+mu)^dagger, so every number a run reads
differs from seed to seed while the operator's spectrum, and with it the
iterations a solve takes, does not (PERF.md section 4: with links drawn
afresh per seed the iterations, and a device-bound cell's seconds,
spread 8 % from seed to seed, and past kappa 0.30 some seeds do not
converge at all).  Layout: matrix/spin/colour indices LEAD, the
lattice trails as (T, Z, Y*X) — every operation is elementwise over the
lattice, and with Y and X merged the minor axis (576 at 24^4) pads only
to 640 on a TPU, where a minor axis of 24 pads 5.3x.  ``lattice`` is the
array order (T, Z, Y, X).  ``to_canonical_*`` give the
(…, T, Z, Y, X, 3, 3) / (…, T, Z, Y, X, 4, 3) arrays the API takes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Gell-Mann matrices lambda_1..lambda_8
_GM = np.zeros((8, 3, 3), np.complex64)
_GM[0, 0, 1] = _GM[0, 1, 0] = 1
_GM[1, 0, 1], _GM[1, 1, 0] = -1j, 1j
_GM[2, 0, 0], _GM[2, 1, 1] = 1, -1
_GM[3, 0, 2] = _GM[3, 2, 0] = 1
_GM[4, 0, 2], _GM[4, 2, 0] = -1j, 1j
_GM[5, 1, 2] = _GM[5, 2, 1] = 1
_GM[6, 1, 2], _GM[6, 2, 1] = -1j, 1j
_GM[7, 0, 0] = _GM[7, 1, 1] = 1 / np.sqrt(3)
_GM[7, 2, 2] = -2 / np.sqrt(3)


def key_of(seed, stream):
    """A PRNG key from any whole-number seed (the driver's pass 2**31)
    and a stream number; x64 is off, so the seed goes in as two words."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, stream)


def merged(lattice):
    t, z, y, x = (int(d) for d in lattice)
    return (t, z, y * x)


def _matmul3(a, b):
    """(3,3,...) x (3,3,...) elementwise over the trailing axes: three
    broadcast multiplies, no dot (so no MXU pass and no padding)."""
    return sum(a[:, j][:, None] * b[j][None, :] for j in range(3))


def shift(v, mu, sign, nx):
    """v(x + sign*mu_hat), periodic; lattice axes (T, Z, Y*X), ``nx`` the
    x extent; mu = 0..3 = x, y, z, t."""
    if mu >= 2:                               # z, t: axes of their own
        return jnp.roll(v, -sign, axis=-2 if mu == 2 else -3)
    if mu == 1:                               # y: a whole row of x
        return jnp.roll(v, -sign * nx, axis=-1)
    x = jnp.arange(v.shape[-1]) % nx          # x: wrap inside the row
    edge = (x == nx - 1) if sign > 0 else (x == 0)
    return jnp.where(edge, jnp.roll(v, sign * (nx - 1), axis=-1),
                     jnp.roll(v, -sign, axis=-1))


@functools.partial(jax.jit, static_argnames=("lead", "lattice", "scale"))
def su3_field(key, lead, lattice, scale):
    """SU(3) matrices exp(i*scale*H), H = sum_a xi_a lambda_a/2 with
    xi ~ N(0,1): complex64 (3, 3) + lead + (T, Z, Y*X).  Taylor order 16
    on H/64, then six squarings."""
    xi = jax.random.normal(key, (8,) + lead + merged(lattice), jnp.float32)
    gen = jnp.asarray(_GM / 2.0)
    n = len(lead) + 3
    h = sum(gen[a][(...,) + (None,) * n] * xi[a][None, None]
            for a in range(8))
    x = (1j * scale / 64.0) * h
    eye = jnp.broadcast_to(
        jnp.eye(3, dtype=jnp.complex64)[(...,) + (None,) * n], x.shape)

    def taylor(k, c):
        term, acc = c
        term = _matmul3(term, x) / k.astype(jnp.float32)
        return term, acc + term
    _, acc = jax.lax.fori_loop(1, 16, taylor, (eye, eye))
    return jax.lax.fori_loop(0, 6, lambda _, m: _matmul3(m, m), acc)


@functools.partial(jax.jit, static_argnames=("nx",))
def gauge_rotate(links, g, nx):
    """U'_mu(x) = g(x) U_mu(x) g(x+mu)^dagger; links (3,3,4,T,Z,Y*X),
    g (3,3,T,Z,Y*X)."""
    out = []
    for mu in range(4):
        gd = jnp.conj(jnp.swapaxes(shift(g, mu, +1, nx), 0, 1))
        out.append(_matmul3(_matmul3(g, links[:, :, mu]), gd))
    return jnp.stack(out, axis=2)


def links_for(seed, traffic, lattice):
    """The links a run of ``seed`` solves: the traffic's configuration
    (hot links exp(i*link_scale*H) from ``gauge_seed``; direction mu =
    x,y,z,t), rotated by the seed's g(x)."""
    base = su3_field(key_of(traffic["gauge_seed"], 0), (4,), lattice,
                     float(traffic["link_scale"]))
    g = su3_field(key_of(seed, 1), (), lattice, 1.0)
    return gauge_rotate(base, g, lattice[3])


@functools.partial(jax.jit, static_argnames=("lattice", "n"))
def gaussian_sources(key, lattice, n):
    """n Gaussian sources, complex64 (n, 4, 3, T, Z, Y*X)."""
    re_im = jax.random.normal(key, (2, n, 4, 3) + merged(lattice),
                              jnp.float32)
    return jax.lax.complex(re_im[0], re_im[1])


@functools.partial(jax.jit, static_argnames=("lattice",))
def to_canonical_gauge(u, lattice):
    """(3,3,4,T,Z,Y*X) -> (4,T,Z,Y,X,3,3), what load_gauge_quda takes."""
    u = u.reshape(u.shape[:3] + tuple(lattice))
    return jnp.transpose(u, (2, 3, 4, 5, 6, 0, 1))


@functools.partial(jax.jit, static_argnames=("lattice",))
def to_canonical_spinors(psi, lattice):
    """(n,4,3,T,Z,Y*X) -> (n,T,Z,Y,X,4,3)."""
    psi = psi.reshape(psi.shape[:3] + tuple(lattice))
    return jnp.transpose(psi, (0, 3, 4, 5, 6, 1, 2))


@jax.jit
def from_canonical_spinors(psi):
    """(n,T,Z,Y,X,4,3) -> (n,4,3,T,Z,Y*X)."""
    psi = jnp.transpose(psi, (0, 5, 6, 1, 2, 3, 4))
    return psi.reshape(psi.shape[:5] + (-1,))
