"""Layer 'compile reuse': programs jax compiled during set-up because its
persistent cache held no entry for them (jax.monitoring's count)."""


def read(obs):
    return obs["setup"]["cache_misses"]
