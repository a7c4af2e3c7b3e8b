"""Plain reference of ``mixtral-8x7b-w1-d50``: the operand as a dense
float32 matrix, made on the device.

One expert's w1 of Mixtral-8x7B, 14336 x 4096, drawn from N(0, 0.02) (the
published ``initializer_range``) and pruned by magnitude, unstructured: the
``density`` share of largest |w| of each row stay, wherever they lie in the
row. The matrix is one fixed checkpoint, drawn from ``pattern_seed``; a
run's seed varies only its right-hand sides, so the key is not used. The
product the reference computes is ``check.gaps``' dense float32 matmul.
"""
import jax
import jax.numpy as jnp


def operand(cfg: dict, key) -> jnp.ndarray:
    del key                        # one checkpoint, whatever the run's seed
    m, k = int(cfg["intermediate_size"]), int(cfg["hidden_size"])
    keep = int(round(k * float(cfg["sparsity"]["density"])))
    w = jax.random.normal(jax.random.PRNGKey(int(cfg["pattern_seed"])),
                          (m, k), jnp.float32) \
        * float(cfg["initializer_range"])
    mag = jnp.abs(w)
    # Each row keeps its ``keep`` largest |w|: those at or above the
    # keep-th largest (ties have probability 0 for normal draws).
    cut = jax.lax.top_k(mag, keep)[0][:, -1:]
    return jnp.where(mag >= cut, w, 0.0)
