"""repro — Greenformer (factorization toolkit) as a JAX/TPU training and
serving framework.

Public one-liner API, mirroring the paper:

    from repro import auto_fact
    fact_model = auto_fact(model, rank=128, solver='svd', num_iter=50)
"""

__all__ = ["auto_fact", "defactorize", "r_max", "resolve_rank"]
__version__ = "1.0.0"


def __getattr__(name):
    # lazy, so that importing a jax-free submodule (the loadgen client)
    # does not import jax
    if name in __all__:
        import repro.core

        return getattr(repro.core, name)
    raise AttributeError(f"module 'repro' has no attribute {name!r}")
