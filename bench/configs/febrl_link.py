"""Febrl-shaped linkage of two person registries (Christen, 2008).

The Febrl data generator's dataset3 settings, scaled twentyfold: 40,000
original person records and 60,000 duplicates, at most 5 duplicates of one
original, the number of duplicates of a duplicated original drawn from
Febrl's Zipf distribution (P(d) proportional to 1/d).  The 100,000 records
are split at random into two registries of 50,000, so a person with k
records has k_a of them in one registry and k_b in the other.

Every record is an embedding at D = 300: its person's point plus noise
that stands for Febrl's per-field and per-record modifications, then
L2-normalised.  A person's point lies on a line of its own, except for the
members of a household (or of a common name): several persons on one line,
each offset from it, so that their records score near the threshold
against each other.  The structure (who has which records, who shares a
household, each record's noise) comes from numpy; the embeddings are made
on the device in one jitted call.

The deployment is one fixed pair of registries: a pool holds four fixed
draws of its shape (``instance_seeds``), and ``--seed`` puts the records
of each registry in another order.  Every seed serves the same work under
other record ids, since how many labelling rounds a session takes (each a
pass of the round engine over every lane) is set by its rarest household
configurations, and that would otherwise change with the seed."""
from __future__ import annotations

import functools

import numpy as np


def _febrl_duplicates(rng, n_entities: int, n_dup: int, max_dup: int):
    """Duplicates per original: originals are taken in random order and
    each given d duplicates, P(d) proportional to 1/d on 1..max_dup, until
    ``n_dup`` duplicates exist (the last one cut to fit)."""
    p = 1.0 / np.arange(1, max_dup + 1)
    d = rng.choice(np.arange(1, max_dup + 1), n_entities, p=p / p.sum())
    cum = np.cumsum(d)
    if cum[-1] < n_dup:
        raise ValueError(f"{n_entities} originals cannot carry {n_dup} "
                         f"duplicates at most {max_dup} each")
    last = int(np.searchsorted(cum, n_dup))
    d[last] -= cum[last] - n_dup
    d[last + 1:] = 0
    out = np.zeros(n_entities, np.int64)
    out[rng.permutation(n_entities)] = d
    return out


def _households(rng, n_entities: int, share: float, sizes):
    """Line id and household size of each person: a ``share`` of the
    persons are cut into households of ``sizes[0]..sizes[1]`` persons that
    share a line; everyone else has a line of their own."""
    lo, hi = sizes
    members = rng.permutation(n_entities)[:int(round(share * n_entities))]
    cuts = np.cumsum(rng.integers(lo, hi + 1, len(members) // lo + 1))
    cuts = cuts[cuts < len(members)]
    if len(cuts) and len(members) - cuts[-1] < lo:
        cuts = cuts[:-1]   # a short last household joins the one before
    hh = np.zeros(len(members), np.int64)
    hh[cuts] = 1
    hh = np.cumsum(hh)
    line = np.empty(n_entities, np.int64)
    singles = np.ones(n_entities, bool)
    singles[members] = False
    n_hh = int(hh[-1]) + 1 if len(members) else 0
    line[members] = hh
    line[singles] = n_hh + np.arange(int(singles.sum()))
    size = np.bincount(line, minlength=n_hh + int(singles.sum()))[line]
    return line, size, n_hh + int(singles.sum())


def structure(spec: dict, seed) -> dict:
    """Entity ids of both registries' records, lines and offsets of the
    persons, and each record's noise, from ``seed``."""
    rng = np.random.default_rng(seed)
    n_a, n_b, E = spec["n_a"], spec["n_b"], spec["n_entities"]
    N = n_a + n_b
    dups = _febrl_duplicates(rng, E, N - E, spec["max_duplicates"])
    ent = np.repeat(np.arange(E), 1 + dups)[rng.permutation(N)]
    line, hh_size, n_lines = _households(rng, E, spec["household_share"],
                                         spec["household_size"])
    lo, hi = spec["rho_household"]
    rho = np.where(hh_size > 1, rng.uniform(lo, hi, E), 0.0)
    lo, hi = spec["sigma_record"]
    return {"ent": ent, "ent_a": ent[:n_a], "ent_b": ent[n_a:],
            "line": line, "rho": rho.astype(np.float32),
            "sigma": rng.uniform(lo, hi, N).astype(np.float32),
            "n_lines": n_lines, "key": int(rng.integers(2 ** 31))}


@functools.partial(__import__("jax").jit, static_argnames=("n_lines", "dim"))
def _embed(key, line, rho, ent, sigma, n_lines: int, dim: int):
    import jax
    import jax.numpy as jnp

    def unit(x):
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    kc, kh, kg = jax.random.split(key, 3)
    cent = unit(jax.random.normal(kc, (n_lines, dim), jnp.float32))
    h = unit(jax.random.normal(kh, (line.shape[0], dim), jnp.float32))
    person = cent[line] + rho[:, None] * h
    g = unit(jax.random.normal(kg, (ent.shape[0], dim), jnp.float32))
    return unit(person[ent] + sigma[:, None] * g)


def generate(spec: dict, seed) -> dict:
    """One session's registries on the device (a, b), with the person id
    of every record on the host."""
    import jax
    import jax.numpy as jnp

    st = structure(spec, seed)
    x = _embed(jax.random.key(st["key"]), jnp.asarray(st["line"]),
               jnp.asarray(st["rho"]), jnp.asarray(st["ent"]),
               jnp.asarray(st["sigma"]), n_lines=st["n_lines"],
               dim=spec["dim"])
    n_a = spec["n_a"]
    return {"a": x[:n_a], "b": x[n_a:], "ent_a": st["ent_a"],
            "ent_b": st["ent_b"], "threshold": spec["threshold"]}


def pool(spec: dict, seed: int, n: int) -> list:
    """``n`` sessions over the fixed draws ``spec["instance_seeds"]``, the
    records of both registries of each put in an order drawn from
    ``seed``: every seed serves the same work under other ids."""
    seeds = spec["instance_seeds"]
    out = []
    for k in range(n):
        base = generate(spec, seeds[k % len(seeds)])
        rng = np.random.default_rng([seed, k])
        pa = rng.permutation(spec["n_a"])
        pb = rng.permutation(spec["n_b"])
        out.append({**base, "a": base["a"][pa], "b": base["b"][pb],
                    "ent_a": base["ent_a"][pa], "ent_b": base["ent_b"][pb]})
    return out
