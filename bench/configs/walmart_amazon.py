"""Walmart-Amazon-shaped two-collection join (Magellan/DeepMatcher).

2,554 Walmart (a) and 22,074 Amazon (b) records, 962 one-to-one matches.
Every record lies on a product line: a unit centroid plus noise, then
L2-normalised at D = 300.  Each Walmart record has a line of its own; its
match (if it has one) lies on the same line, and a share of the unmatched
Amazon records are near-variants on some Walmart line, a different entity
that scores near the threshold against it.  The rest of Amazon lies on
lines of its own.  The structure (which records match, which are
variants of which line) comes from numpy; the embeddings are made on the
device in one jitted call."""
from __future__ import annotations

import functools

import numpy as np


def structure(spec: dict, seed) -> dict:
    """Entity ids and product lines of both sides, from ``seed``."""
    rng = np.random.default_rng(seed)
    n_a, n_b, n_m = spec["n_a"], spec["n_b"], spec["n_matches"]
    n_var = int(round(spec["variant_share"] * (n_b - n_m)))
    matched_a = rng.choice(n_a, n_m, replace=False)
    line_b = np.empty(n_b, np.int64)
    ent_b = np.empty(n_b, np.int64)
    sigma_b = np.full(n_b, spec["sigma_record"], np.float32)
    line_b[:n_m] = matched_a
    ent_b[:n_m] = matched_a
    var = slice(n_m, n_m + n_var)
    line_b[var] = rng.integers(0, n_a, n_var)
    lo, hi = spec["sigma_variant"]
    sigma_b[var] = rng.uniform(lo, hi, n_var)
    rest = n_b - n_m - n_var
    line_b[n_m + n_var:] = n_a + np.arange(rest)
    ent_b[n_m:] = n_a + np.arange(n_b - n_m)
    perm = rng.permutation(n_b)
    return {"ent_a": np.arange(n_a), "ent_b": ent_b[perm],
            "line_b": line_b[perm], "sigma_b": sigma_b[perm],
            "n_lines": n_a + rest, "key": int(rng.integers(2 ** 31))}


@functools.partial(__import__("jax").jit, static_argnames=("n_lines", "dim"))
def _embed(key, line_b, sigma_b, sigma_a, n_lines: int, dim: int):
    import jax
    import jax.numpy as jnp

    kc, ka, kb = jax.random.split(key, 3)
    cent = jax.random.normal(kc, (n_lines, dim), jnp.float32)
    cent = cent / jnp.linalg.norm(cent, axis=1, keepdims=True)

    def noisy(k, lines, sigma):
        g = jax.random.normal(k, (lines.shape[0], dim), jnp.float32)
        g = g / jnp.linalg.norm(g, axis=1, keepdims=True)
        x = cent[lines] + sigma[:, None] * g
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    n_a = sigma_a.shape[0]
    return (noisy(ka, jnp.arange(n_a), sigma_a),
            noisy(kb, line_b, sigma_b))


def generate(spec: dict, seed) -> dict:
    """One session's embeddings on the device (a, b), with entity ids of
    both sides on the host."""
    import jax
    import jax.numpy as jnp

    st = structure(spec, seed)
    sigma_a = np.full(spec["n_a"], spec["sigma_record"], np.float32)
    a, b = _embed(jax.random.key(st["key"]), jnp.asarray(st["line_b"]),
                  jnp.asarray(st["sigma_b"]), jnp.asarray(sigma_a),
                  n_lines=st["n_lines"], dim=spec["dim"])
    return {"a": a, "b": b, "ent_a": st["ent_a"], "ent_b": st["ent_b"],
            "threshold": spec["threshold"]}


def pool(spec: dict, seed: int, n: int) -> list:
    """``n`` independent sessions drawn from ``seed``."""
    return [generate(spec, [seed, k]) for k in range(n)]
