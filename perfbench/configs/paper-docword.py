"""Plain reference of ``paper-docword``: the paper's Table II docword
operand (700 x 12,000, density 0.04) as a dense float32 matrix, made on the
device.

The pattern is one fixed matrix, as a data set is: row lengths are
lognormal within the stated (min, mean, max) and rescaled to the density,
and each row's columns are drawn without replacement by a Zipf-like
column popularity (Gumbel top-k), all from ``pattern_seed``. The values are
uniform in ``values`` from the run's key.
"""
import jax
import jax.numpy as jnp


def operand(cfg: dict, key) -> jnp.ndarray:
    m, n = int(cfg["rows"]), int(cfg["cols"])
    lo, avg, hi = (float(x) for x in cfg["row_nnz"])
    k_deg, k_perm, k_gum = jax.random.split(
        jax.random.PRNGKey(int(cfg["pattern_seed"])), 3)
    sigma = 0.6 if hi > 3 * avg else 0.3
    mu = jnp.log(avg) - sigma * sigma / 2
    deg = jnp.clip(jnp.exp(mu + sigma * jax.random.normal(k_deg, (m,))),
                   lo, hi)
    deg = jnp.clip(deg * (round(m * n * float(cfg["density"])) / deg.sum()),
                   lo, hi)
    deg = jnp.maximum(1, jnp.round(deg)).astype(jnp.int32)
    pop = 1.0 / jnp.arange(1, n + 1, dtype=jnp.float32) \
        ** float(cfg["column_skew"])
    pop = 0.5 * pop / pop.sum() + 0.5 / n
    pop = pop[jax.random.permutation(k_perm, n)]
    g = jnp.log(pop)[None, :] + jax.random.gumbel(k_gum, (m, n))
    # Each row keeps its deg largest keys: those at or above the deg-th.
    top = -jnp.sort(-g, axis=1)
    cut = jnp.take_along_axis(top, (deg - 1)[:, None], axis=1)
    v_lo, v_hi = (float(x) for x in cfg["values"])
    vals = jax.random.uniform(key, (m, n), jnp.float32, v_lo, v_hi)
    return jnp.where(g >= cut, vals, 0.0)
