"""Seconds JAX spent in backend compilation (or reading the persistent
cache) during set-up, from ``jax.monitoring``'s compile events."""


def read(obs):
    return obs["compile_setup"]["seconds"]
