"""Public jit'd entry points for the kernel package.

Backend selection:
  * ``pallas``           — real pl.pallas_call (TPU target),
  * ``pallas_interpret`` — kernel body interpreted on CPU (bit-identical
                           semantics, used by tests/CI in this container),
  * ``ref``              — the pure-jnp oracle (fast on CPU; what the
                           functional CUTIE engine uses by default here).

Default: ``pallas`` when a TPU is present, else ``ref``.  Override with the
``REPRO_KERNEL_BACKEND`` env var or the ``backend=`` kwarg.
"""

from __future__ import annotations

import os

import jax

from repro.kernels import ref as _ref
from repro.kernels import ternary_conv2d as _conv
from repro.kernels import ternary_matmul as _mm
from repro.kernels import trit_codec as _codec


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return env
    # No fallback: a JAX backend that fails to initialise raises here.
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


def _interp(backend: str) -> bool:
    return backend == "pallas_interpret"


def ternary_matmul(x, w_packed, *, scale=None, t_lo=None, t_hi=None,
                   flip=None, backend: str | None = None, **blocks):
    """Packed-weight ternary matmul with optional fused epilogue."""
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.ternary_matmul(x, w_packed, scale=scale, t_lo=t_lo,
                                   t_hi=t_hi, flip=flip)
    return _mm.ternary_matmul_pallas(
        x, w_packed, scale=scale, t_lo=t_lo, t_hi=t_hi, flip=flip,
        interpret=_interp(backend), **blocks)


def ternary_matmul_dense(x, w, *, backend: str | None = None, **blocks):
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.ternary_matmul_dense(x, w)
    return _mm.ternary_matmul_dense_pallas(
        x, w, interpret=_interp(backend), **blocks)


def ternary_conv2d(x, w, *, stride=(1, 1), padding=True, t_lo=None,
                   t_hi=None, flip=None, backend: str | None = None,
                   **blocks):
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.ternary_conv2d(x, w, stride=stride, padding=padding,
                                   t_lo=t_lo, t_hi=t_hi, flip=flip)
    return _conv.ternary_conv2d_pallas(
        x, w, stride=stride, padding=padding, t_lo=t_lo, t_hi=t_hi,
        flip=flip, interpret=_interp(backend), **blocks)


def pack_trits(t, *, backend: str | None = None):
    """(R, 5G) -> (R, G) uint8."""
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.pack_trits(t)
    return _codec.pack_trits_pallas(t, interpret=_interp(backend))


def unpack_trits(b, *, backend: str | None = None):
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.unpack_trits(b)
    return _codec.unpack_trits_pallas(b, interpret=_interp(backend))


def thermometer(x, m: int, *, ternary: bool = True,
                backend: str | None = None):
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.thermometer(x, m, ternary=ternary)
    return _codec.thermometer_pallas(x, m, ternary=ternary,
                                     interpret=_interp(backend))
