"""What the family files' remat tests share (``tests/test_*_remat.py``,
``tests/test_*_engine.py``): a causal LM's gradients with remat off and on,
each a single compiled program (``tests/conftest.py``'s rule)."""

import jax

from tests.hlo_text import run_with_jaxpr


def gradients_without_and_with_remat(model_of, ids):
    """((gradients, jaxpr text) of ``model_of(False)``'s loss on ``ids``, the
    same of ``model_of(True)``) at one set of weights — remat names no
    parameter, so one init serves both — each model one compiled program."""
    params = jax.jit(model_of(False).init)(jax.random.PRNGKey(0),
                                           ids)["params"]

    def grads(remat):
        model = model_of(remat)
        return run_with_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)), params)
    return grads(False), grads(True)
