"""What the references share: the matmul at a stated precision, the
logistic loss, and the minibatch draw."""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: "highest": f32 operands, as the configurations state.  "bf16x3": the
#: nearest precision below, XLA's "high" (each f32 operand split into a
#: bf16 high part and a bf16 low part, the low×low product dropped).  On
#: a TPU that is ``Precision.HIGH``, XLA's own three-pass product, unless
#: ``written_out``; other backends ignore ``HIGH``, so there it is always
#: written out.  XLA runs a product with a 1-D operand in f32 whatever its
#: precision, so a reference made of such products writes it out.
PRECISIONS = ("highest", "bf16x3")


def _bf16(v):
    """``v`` rounded to bf16's 8-bit mantissa, kept in f32."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _split(v):
    hi = _bf16(v)
    return hi, _bf16(v - hi)


def dot(a, b, precision: str, written_out: bool = False):
    """``a @ b`` with f32 accumulation at ``precision``."""
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hp)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu" and not written_out:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    ah, al = _split(a)
    bh, bl = _split(b)
    return (jnp.matmul(ah, bh, precision=hp)
            + (jnp.matmul(ah, bl, precision=hp)
               + jnp.matmul(al, bh, precision=hp)))


def logistic_loss(agg, y):
    return jnp.logaddexp(0.0, -y * agg)


def logistic_theta(agg, y):
    """dloss/dagg, the value the dominator broadcasts (BUM)."""
    return -y * jax.nn.sigmoid(-y * agg)


def batch_indices(key, n: int, batch: int, steps: int):
    """Minibatches drawn uniformly with replacement: (steps, batch)."""
    return jax.random.randint(key, (steps, batch), 0, n)
