"""TPL011 positive: a strong (non-weak) float64 constant in a traced
function. tests/test_ircheck.py traces ``build``'s function under
``jax.enable_x64`` and runs
``analysis.ircheck.f64_findings`` over the jaxpr; the ``np.float64``
scalar is a committed dtype (``weak_type=False``), so the multiply
lowers as f64 — exactly the widening TPL011 rejects."""

import numpy as np


def build(jax, jnp):
    def fn(x):
        # EXPECT: TPL011
        return x * np.float64(2.5)

    return fn, (jnp.ones((4,), jnp.float32),)
