"""Seeded synthetic data, made on the device.

The rows are ``blobs`` Gaussian blobs: a center drawn N(0, center_scale^2)
per dimension, plus noise whose scale falls along the dimensions as
``noise_scale * (1 + j) ** -noise_power``, so that most of a blob's
variance lies in its first dimensions, as in PCA-reduced descriptors such
as DEEP's. Row ``i`` belongs to blob ``i % blobs``; rows are cast to the
stored dtype. Queries are fresh draws from the same law (a blob drawn
uniformly, then its noise), in float32: no query is near a copy of a row,
so a query's neighbours spread over several of its blob's IVF lists and
recall depends on how many lists a search probes.

The same seed gives the same rows and queries. Seeds may exceed 32 bits:
the high word is folded into the key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the configuration keys that define the data
KEYS = ("rows", "dim", "blobs", "center_scale", "noise_scale",
        "noise_power", "make_block", "dtype")


def base_key(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (``PRNGKey``
    alone keeps only the low 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _law(key, blobs, dim, center_scale, noise_scale, noise_power):
    centers = center_scale * jax.random.normal(jax.random.fold_in(key, 1),
                                               (blobs, dim), jnp.float32)
    scales = noise_scale * (1.0 + jnp.arange(dim, dtype=jnp.float32)) ** (
        -noise_power)
    return centers, scales


@functools.partial(jax.jit, static_argnames=KEYS)
def _make_rows(key, *, rows, dim, blobs, center_scale, noise_scale,
               noise_power, make_block, dtype):
    centers, scales = _law(key, blobs, dim, center_scale, noise_scale,
                           noise_power)
    parts = []
    for b in range(rows // make_block):
        ids = b * make_block + jnp.arange(make_block)
        noise = jax.random.normal(jax.random.fold_in(key, 1000 + b),
                                  (make_block, dim))
        parts.append((centers[ids % blobs] + scales * noise).astype(dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _params(cfg):
    out = {k: cfg[k] for k in KEYS}
    out["dtype"] = jnp.dtype(out["dtype"]).name
    return out


def _key(seed, device):
    key = base_key(seed)
    return key if device is None else jax.device_put(key, device)


def make_rows(seed: int, cfg: dict, device=None):
    """(rows, dim) rows of the configuration's dtype made on ``device`` by
    one jitted call (``rows`` must be a multiple of ``make_block``)."""
    if cfg["rows"] % cfg["make_block"]:
        raise ValueError(f"rows={cfg['rows']} is not a multiple of "
                         f"make_block={cfg['make_block']}")
    return _make_rows(_key(seed, device), **_params(cfg))


@functools.partial(jax.jit, static_argnames=KEYS + ("count",))
def _make_queries(key, *, count, rows, dim, blobs, center_scale,
                  noise_scale, noise_power, make_block, dtype):
    centers, scales = _law(key, blobs, dim, center_scale, noise_scale,
                           noise_power)
    blob = jax.random.randint(jax.random.fold_in(key, 98), (count,), 0,
                              blobs)
    noise = jax.random.normal(jax.random.fold_in(key, 99), (count, dim),
                              jnp.float32)
    return centers[blob] + scales * noise


def make_queries(seed: int, cfg: dict, count: int, device=None):
    """(count, dim) float32 queries drawn from the rows' law, on
    ``device``."""
    return _make_queries(_key(seed, device), count=count, **_params(cfg))
