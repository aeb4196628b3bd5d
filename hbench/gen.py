"""The traffic generator: memory-request streams and serving populations
made from a seed, as a traffic file's parameters describe them.

The request patterns are copies of the repository's synthetic generators
(zipfian reuse, sequential streaming), kept here so that a change to the
program cannot change the yardstick. They take the PRNG key as a traced
argument, so one compiled program makes every segment of every seed.
Given ``jax.random.PRNGKey(s)`` they give the arrays the repository's
generator gives for ``TraceSpec(seed=s)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LINE = 64
PAGE = 4096


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError("seeds are non-negative")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _writes(key, n, write_frac):
    return jax.random.uniform(key, (n,)) < write_frac


def _offsets(key, n):
    lines = PAGE // LINE
    return (jax.random.randint(key, (n,), 0, lines) * LINE).astype(jnp.int32)


def _zipf_pages(key, n, footprint, alpha):
    """Zipfian page popularity by inverse-CDF sampling on ranks, the ranks
    scattered over the footprint so hot pages are not contiguous."""
    ranks = jnp.arange(1, footprint + 1, dtype=jnp.float32)
    w = ranks ** -alpha
    cdf = jnp.cumsum(w) / jnp.sum(w)
    u = jax.random.uniform(key, (n,))
    pages = jnp.searchsorted(cdf, u).astype(jnp.int32)
    perm = jax.random.permutation(jax.random.fold_in(key, 7), footprint)
    return perm[jnp.clip(pages, 0, footprint - 1)].astype(jnp.int32)


def zipfian(key, n, footprint, alpha, write_frac):
    k1, k2, k3 = jax.random.split(key, 3)
    return (_zipf_pages(k1, n, footprint, alpha), _offsets(k2, n),
            _writes(k3, n, write_frac), jnp.full(n, LINE, jnp.int32))


def sequential(key, n, footprint, write_frac):
    _, k3 = jax.random.split(key)
    lines = PAGE // LINE
    idx = jnp.arange(n)
    return (((idx // lines) % footprint).astype(jnp.int32),
            ((idx % lines) * LINE).astype(jnp.int32),
            _writes(k3, n, write_frac), jnp.full(n, LINE, jnp.int32))


PATTERNS = {"zipfian": zipfian, "sequential": sequential}


@functools.partial(jax.jit, static_argnames=("n_segments", "n", "pattern",
                                             "args"))
def _segments(key, *, n_segments, n, pattern, args):
    keys = jax.vmap(lambda k: jax.random.fold_in(key, k))(
        jnp.arange(n_segments))
    return jax.vmap(lambda k: PATTERNS[pattern](k, n, *args))(keys)


def segments(seed: int, stream: dict, n_segments: int):
    """``n_segments`` distinct request segments of ``stream["requests"]``
    requests each, made on the default device in one call. Returns
    ``(page, offset, is_write, size)`` with a leading segment axis."""
    pattern = stream["pattern"]
    footprint = stream["footprint_bytes"] // PAGE
    if pattern == "zipfian":
        args = (footprint, stream["zipf_alpha"], stream["write_frac"])
    elif pattern == "sequential":
        args = (footprint, stream["write_frac"])
    else:
        raise ValueError(f"unknown request pattern {pattern!r}")
    return _segments(seed_key(seed), n_segments=n_segments,
                     n=stream["requests"], pattern=pattern, args=args)


def population(seed: int, mix: dict, n: int):
    """``n`` serving sequences: prompt pages drawn from
    ``mix["prompt_pages"]`` with ``mix["prompt_p"]``, decode tokens
    uniform in ``[decode_lo, decode_hi)``. Returns (prompt, decode)."""
    rng = np.random.default_rng(seed)
    prompt = rng.choice(mix["prompt_pages"], size=n, p=mix["prompt_p"])
    decode = rng.integers(mix["decode_lo"], mix["decode_hi"], size=n)
    return prompt.astype(np.int32), decode.astype(np.int32)
