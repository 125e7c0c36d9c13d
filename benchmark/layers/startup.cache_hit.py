"""1 when the training step's executable came out of the persistent
compilation cache (a ``jax.monitoring`` cache hit between the first
dispatch and the end of the first chunk), else 0."""


def read(run):
    hit = run.facts.get("megastep_cache_hit")
    return None if hit is None else float(bool(hit))
