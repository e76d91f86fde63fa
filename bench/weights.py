"""Random served weights for a configuration, made on the device from a seed.

The tree and dtypes are the serving program's own packed layout, read with
``jax.eval_shape`` (nothing is computed on the host), and every leaf is
drawn in one jitted call in the type it is served in: base-3 codes as
uint8, scales and gains as float32, the embedding and an untied head as
float32.  Codes are uniform over the 3**5 codes of five ternary weights;
rows past a matrix's fan-in, which the layout pads, hold the code of five
zero weights.  The fan-ins, and any leaf these rules do not cover, are the
configuration's architecture's (``bench/archs``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.archs import arch
from repro.configs.base import ModelConfig
from repro.models import transformer


def model_config(cfg: dict) -> ModelConfig:
    """The program's config object for a ``bench/configs`` file."""
    return arch(cfg).model_config(cfg)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _codes(key, shape, n_in: int, group: int):
    """Uniform base-3 codes with every weight past ``n_in`` set to 0."""
    codes = jax.random.randint(key, shape, 0, 3 ** group, jnp.int32)
    rows = jnp.arange(shape[-2])[:, None]
    live = jnp.clip(n_in - rows * group, 0, group)        # live digits
    low = 3 ** live                                       # keep those ...
    codes = codes % low + (3 ** group - low) // 2         # ... rest digit 1
    return codes.astype(jnp.uint8)


def _is_norm(owner) -> bool:
    """The program names a norm's owner ``ln*`` or ``*norm``."""
    return str(owner).startswith("ln") or str(owner).endswith("norm")


def _leaf(cfg: dict, path: tuple, shape, dtype, key):
    """One served leaf by the rules every architecture shares; a leaf they
    do not cover goes to the architecture's own ``leaf``, where it has
    one."""
    names = [getattr(p, "key", None) for p in path]
    leaf, owner = names[-1], names[-2]
    mod = arch(cfg)
    if leaf == "codes":
        return _codes(key, shape, mod.fan_in(cfg)[owner], cfg["pack_group"])
    if leaf == "gamma":
        base = 0.8 / jnp.sqrt(jnp.float32(mod.fan_in(cfg)[owner]))
        return base * jax.random.uniform(key, shape, jnp.float32, 0.9, 1.1)
    if leaf == "w" and _is_norm(owner):
        return jnp.ones(shape, dtype)
    if leaf == "tok":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if leaf == "w" and owner == "lm_head":
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(shape[0]))).astype(dtype)
    if leaf == "b":
        return jnp.zeros(shape, dtype)
    rule = getattr(mod, "leaf", None)
    value = rule(names, shape, dtype, key) if rule else None
    if value is None:
        raise ValueError(f"no rule to draw weight {names}")
    return value


def served_shapes(cfg: dict):
    mcfg = model_config(cfg)
    return jax.eval_shape(
        lambda k: transformer.pack_params(mcfg, transformer.init_params(
            mcfg, k)), jax.random.PRNGKey(0))


def make_weights(cfg: dict, seed: int):
    """The served packed weights for ``seed``, on the default device."""
    shapes = served_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(cfg, path, s.shape, s.dtype, k)
            for (path, s), k in zip(paths, keys)])

    return draw(seed_key(seed))
